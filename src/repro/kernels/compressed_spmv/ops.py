"""jit'd public wrappers around the compressed_spmv Pallas kernel.

Three entry points, one per streaming discipline:

* ``compressed_spmv_vertex`` (+``_batched``) — the dense grid: every block's
  compressed tile streams HBM→VMEM once, fused decode + masked SpMV, with
  the rare ESCAPE blocks recomputed exactly and patched afterwards.
* ``compressed_spmv_vertex_chunked`` — the frontier-sparse chunked mode:
  only blocks owned by ``frontier`` vertices stream, driven by the
  scalar-prefetched compacted live-id list (``PrefetchScalarGridSpec``);
  handles single and (B, n)-batched vertex state.
* ``compressed_chunked_stream_tile`` — the chunk-pool decoder behind the
  core ``edgemap_chunked`` streamed path: one chunk of live ids in, exact
  masked targets + aligned weights out, exceptions patched by gathered id.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ...core.compressed import CompressedCSR, decode_block, exception_dense
from ...core.graph_filter import (
    GraphFilter,
    edge_active_words,
    make_filter,
    unpack_word_bits,
)
from ...core.primitives import compact_mask
from ...tuning.defaults import DEFAULT_TILE_BLOCKS
from .compressed_spmv import (
    compressed_block_spmv_pallas,
    compressed_chunked_spmv_pallas,
)
from .ref import compressed_block_spmv_ref, compressed_chunked_spmv_ref


def compressed_block_spmv(
    x,
    block_first,
    deltas,
    valid_count,
    bits,
    edge_active=None,
    block_weights=None,
    *,
    n: int,
    interpret: bool | None = None,
    tile_blocks: int = DEFAULT_TILE_BLOCKS,
):
    """Raw kernel entry: per-block partial sums off the compressed stream.

    The array-level form of ``compressed_spmv_vertex`` without the owner
    reduction or the exception fixup — callers holding the delta-packed
    arrays directly (benchmarks, tests) get the fused decode+SpMV exactly
    as the kernel computes it, ESCAPE blocks decoded wrong on purpose.
    ``x`` may be (n_pad,) or a (B, n_pad) query batch (→ out (NB, B)).
    """
    return compressed_block_spmv_pallas(
        x,
        block_first,
        deltas,
        valid_count,
        bits,
        edge_active,
        block_weights,
        n=n,
        interpret=interpret,
        tile_blocks=tile_blocks,
    )


def _exception_block_sums(c: CompressedCSR, x, bits, weights=None, active=None):
    """Exact per-block partial sums for the blocks on the exception list.

    ``exc_block`` may repeat a block (several wide gaps in one block), so
    each row is decoded with ``decode_block``, which patches *every*
    exception matching its block id — O(NE² ) integer compares plus
    O(NE · F_B) decode work, no NE×NE×F_B intermediates (App. D.1's rare
    path; the ops-level fallback caps NE before this could dominate).
    ``weights`` rides along as the uncompressed (NB, FB) stream and
    ``active`` as the packed (NB, F_B/32) traversal mask: the exception rows
    gather their aligned weight/mask tiles by block id, so the fixup masks
    exactly what the kernel masked.

    Batched queries (x of shape (B, n_pad)) return (NE, B): the exception
    rows are decoded once and applied across the batch, matching the
    kernel's amortization contract slot for slot.
    """
    ebids = c.exc_block
    dst = jax.vmap(lambda b: decode_block(c, b))(ebids)    # exact decode
    act = unpack_word_bits(jnp.take(bits, ebids, axis=0))
    if active is not None:
        act = act & unpack_word_bits(jnp.take(active, ebids, axis=0))
    mask = (dst < jnp.int32(c.n)) & act
    safe = jnp.where(mask, dst, 0)
    if x.ndim == 2:
        xv = jnp.take(x, safe.reshape(-1), axis=1).reshape(
            x.shape[0], *dst.shape
        )                                                  # (B, NE, FB)
        if weights is not None:
            xv = xv * jnp.take(weights, ebids, axis=0)[None]
        contrib = jnp.where(mask[None], xv, jnp.zeros((), x.dtype))
        return jnp.sum(contrib, axis=2).T                  # (NE, B)
    xv = jnp.take(x, safe.reshape(-1), axis=0).reshape(dst.shape)
    if weights is not None:
        xv = xv * jnp.take(weights, ebids, axis=0)
    contrib = jnp.where(mask, xv, jnp.zeros((), x.dtype))
    return jnp.sum(contrib, axis=1)                        # (NE,)


def compressed_spmv_vertex(
    c: CompressedCSR,
    x: jnp.ndarray,
    f: GraphFilter | None = None,
    *,
    edge_active=None,
    interpret: bool | None = None,
    tile_blocks: int = DEFAULT_TILE_BLOCKS,
) -> jnp.ndarray:
    """out[v] = Σ_{(v,u) active} w_vu · x[u], straight off the compressed
    stream.

    The Pallas kernel fuses the uint16-delta decode with the masked SpMV; the
    rare ESCAPE blocks are then recomputed exactly and patched into the
    per-block sums before the cheap O(#blocks) owner reduction.

    ``edge_active`` is the per-call traversal mask (a GraphFilter, a packed
    uint32 (NB, F_B/32) word array, or a bool edge-slot mask — see
    ``repro.core.graph_filter.edge_active_words``).  It streams into the
    kernel as a second packed bitmask tile and is ANDed with the filter bits
    in VMEM — the filtered fast path never falls back to a full decode, and
    the exception fixup applies the identical mask.

    Weighted graphs keep their weights as a parallel *uncompressed* stream
    (weights don't difference-encode, §5.1.3): the kernel streams the
    aligned (TB, FB) weight tile next to the delta tile and applies it after
    the in-VMEM decode, so the target stream still moves at compressed
    width.  (A fused weight-compression scheme is future work; this is the
    minimal correct fast path.)

    Graphs whose neighbor lists lack id-locality (many true ≥2¹⁶ gaps) make
    the exception list dense; past num_blocks/4 exceptions — or past the
    absolute cap where the O(NE²) tile fixup would dominate — the fused
    stream saves nothing and the exact jnp decode is used instead, a static
    (trace-time) choice since n_exceptions is metadata.  That choice depends
    only on the exception density, never on whether a filter is present.
    """
    bits = f.bits if f is not None else make_filter(c).bits
    active = (
        None
        if edge_active is None
        else edge_active_words(edge_active, c.block_size)
    )
    w = c.block_weights if c.weighted else None
    if exception_dense(c):
        per_block = compressed_block_spmv_ref(c, x, bits, w, active)
    else:
        per_block = compressed_block_spmv_pallas(
            x,
            c.block_first,
            c.deltas,
            c.valid_count,
            bits,
            active,
            w,
            n=c.n,
            interpret=interpret,
            tile_blocks=tile_blocks,
        )
        if c.n_exceptions:
            fixed = _exception_block_sums(c, x, bits, w, active)
            per_block = per_block.at[c.exc_block].set(fixed)
    return jax.ops.segment_sum(per_block, c.block_src, num_segments=c.n + 1)[: c.n]


def _exception_row_targets(c: CompressedCSR, active=None):
    """Exact decoded targets for every exception-list block, active-masked.

    (NE, FB) int32 with inactive slots already at the sentinel ``n`` — the
    same folding the chunked kernel applies in-VMEM, so a patched row is
    indistinguishable from a correctly decoded one."""
    exact = jax.vmap(lambda b: decode_block(c, b))(c.exc_block)
    if active is not None:
        abits = unpack_word_bits(jnp.take(active, c.exc_block, axis=0))
        exact = jnp.where(abits, exact, jnp.int32(c.n))
    return exact


def _rows_for_ids(ids: jnp.ndarray, exc_block: jnp.ndarray, num_blocks: int):
    """For each exception, the row of ``ids`` holding its block (drop: len).

    ``ids`` rows are unique real block ids (compacted indices) plus sentinel
    pad, so argmax-over-match routes each exception to at most one row —
    the existing per-block patch discipline, keyed on the gathered ids.
    Exception rows with ``exc_block >= num_blocks`` are the padding of a
    sharded graph's stacked exception list; without the in-range guard they
    would match the chunk's own sentinel pad (both use the block count as
    fill) and ghost-patch the all-sentinel row."""
    match = (ids[:, None] == exc_block[None, :]) & (
        exc_block[None, :] < jnp.int32(num_blocks)
    )                                                          # (C, NE)
    hit = jnp.any(match, axis=0)
    return jnp.where(hit, jnp.argmax(match, axis=0), ids.shape[0])


def compressed_chunked_stream_tile(
    c: CompressedCSR,
    ids: jnp.ndarray,
    edge_active=None,
    *,
    interpret: bool | None = None,
    exact_rows: jnp.ndarray | None = None,
    gather_tiles: bool = True,
    tile_blocks: int = DEFAULT_TILE_BLOCKS,
):
    """Stream + decode ONE chunk of live blocks: (dst (C, FB), w (C, FB)).

    The Pallas kernel moves only the delta/bitmask/weight tiles named by
    ``ids`` (ids ≥ num_blocks decode to all-sentinel rows), fusing the
    cumsum decode and the packed ``edge_active`` masking in-VMEM; ESCAPE
    blocks are then recomputed exactly and patched keyed on the gathered
    ids.  This is the tile view the core ``edgemap_chunked`` streamed path
    consumes in place of ``tile_block_reader``'s tiles — same contract, but
    the dead blocks' compressed bytes are never read.

    ``exact_rows``: optionally the precomputed
    ``_exception_row_targets(c, words)`` — it is id-independent, so a
    chunk-loop caller computes it ONCE outside the loop and passes it per
    chunk instead of re-decoding every exception block per iteration
    (``_streaming_decoder`` in ``repro.core.edgemap`` does exactly this).

    ``gather_tiles`` (default) batches the live rows into DMA-sized
    ``(tile_blocks, FB)`` kernel tiles instead of the row-steered
    ``(1, FB)`` grid; shapes and results are identical either way.
    """
    active = (
        None
        if edge_active is None
        else edge_active_words(edge_active, c.block_size)
    )
    w = c.block_weights if c.weighted else None
    dst, ws = compressed_chunked_spmv_pallas(
        None,
        ids,
        c.block_first,
        c.deltas,
        c.valid_count,
        None,
        active,
        w,
        n=c.n,
        emit="decode",
        interpret=interpret,
        gather_tiles=gather_tiles,
        tile_blocks=tile_blocks,
    )
    if c.n_exceptions:
        exact = (
            _exception_row_targets(c, active) if exact_rows is None else exact_rows
        )
        rows = _rows_for_ids(ids, c.exc_block, c.num_blocks)
        dst = dst.at[rows].set(exact, mode="drop")
    return dst, ws


def compressed_spmv_vertex_chunked(
    c: CompressedCSR,
    x: jnp.ndarray,
    frontier: jnp.ndarray,
    f: GraphFilter | None = None,
    *,
    edge_active=None,
    tile_blocks: int = DEFAULT_TILE_BLOCKS,
    interpret: bool | None = None,
    gather_tiles: bool = True,
) -> jnp.ndarray:
    """Frontier-sparse SpMV: sums over ONLY the frontier-owned blocks.

    ``out[v] = Σ_{(v,u) active} w_vu · x[u]`` for frontier vertices v, 0
    elsewhere — the per-vertex pull restricted to the blocks the frontier
    touches, which is the PSAM read-volume claim: bytes streamed off the
    compressed array are proportional to the live blocks, not to NB.

    Execution: the live block ids are compacted once (``compact_mask`` over
    ``frontier[block_src]``, an O(n)-word list) and walked in chunks of
    ``tile_blocks``; each chunk is one ``PrefetchScalarGridSpec`` launch of
    ``compressed_chunked_spmv_pallas`` (so the streamed volume is the
    padded chunk count, ``ceil(k / TB) · TB`` blocks), and the chunk loop
    is a dynamic-trip-count ``while_loop`` — chunks past the live count
    never execute.  Exception blocks are patched with the exact per-block
    sums keyed on the gathered ids; exception-dense graphs fall back to the
    masked exact decode (a function of exception density only, as ever).

    ``x`` may be (n,) or a (B, n) query batch — the batch shares each
    chunk's single delta-stream read, returning (B, n).  ``f`` /
    ``edge_active`` behave exactly as in ``compressed_spmv_vertex``.
    """
    bits = f.bits if f is not None else make_filter(c).bits
    active = (
        None
        if edge_active is None
        else edge_active_words(edge_active, c.block_size)
    )
    w = c.block_weights if c.weighted else None
    batched = x.ndim == 2
    if exception_dense(c):
        return compressed_chunked_spmv_ref(c, x, frontier, bits, w, active)

    NB = c.num_blocks
    TB = min(tile_blocks, NB)
    nchunks = -(-NB // TB)
    blk_live = jnp.take(frontier, c.block_src, mode="fill", fill_value=False)
    idx, k = compact_mask(blk_live, fill=NB)
    idx = jnp.pad(idx, (0, nchunks * TB - NB), constant_values=NB)

    fixed = (
        _exception_block_sums(c, x, bits, w, active) if c.n_exceptions else None
    )  # (NE,) or (NE, B): exact sums, same masks as the kernel

    out0 = jnp.zeros(
        (c.n + 1, x.shape[0]) if batched else (c.n + 1,), x.dtype
    )

    def body(state):
        i, out = state
        ids = lax.dynamic_slice(idx, (i * TB,), (TB,))
        sums = compressed_chunked_spmv_pallas(
            x,
            ids,
            c.block_first,
            c.deltas,
            c.valid_count,
            bits,
            active,
            w,
            n=c.n,
            emit="sums",
            interpret=interpret,
            gather_tiles=gather_tiles,
            tile_blocks=TB,
        )  # (TB,) or (TB, B) — only these ids' tiles were streamed
        if fixed is not None:
            rows = _rows_for_ids(ids, c.exc_block, c.num_blocks)
            sums = sums.at[rows].set(fixed, mode="drop")
        srcs = jnp.take(c.block_src, ids, mode="fill", fill_value=c.n)
        out = out + jax.ops.segment_sum(sums, srcs, num_segments=c.n + 1)
        return i + 1, out

    def cond(state):
        i, _ = state
        return (i * TB < k) & (i < nchunks)

    _, out = lax.while_loop(cond, body, (jnp.int32(0), out0))
    out = out[: c.n]
    return out.T if batched else out


def compressed_spmv_vertex_batched(
    c: CompressedCSR,
    xb: jnp.ndarray,
    f: GraphFilter | None = None,
    *,
    edge_active=None,
    interpret: bool | None = None,
    tile_blocks: int = DEFAULT_TILE_BLOCKS,
) -> jnp.ndarray:
    """Batched ``compressed_spmv_vertex``: ``xb`` is (B, n); returns (B, n).

    One sweep of the compressed stream serves all B queries: each grid step
    streams the delta tile (plus masks/weights) into VMEM and runs the fused
    cumsum decode once, fanning only the gather across the B columns — the
    compressed edge-byte reads amortize ÷B.  The ESCAPE-block fixup and the
    exact-decode fallback are vectorized to match, so every query's result
    is bit-identical to its own single-query run."""
    bits = f.bits if f is not None else make_filter(c).bits
    active = (
        None
        if edge_active is None
        else edge_active_words(edge_active, c.block_size)
    )
    w = c.block_weights if c.weighted else None
    if exception_dense(c):
        per_block = compressed_block_spmv_ref(c, xb, bits, w, active)  # (NB, B)
    else:
        per_block = compressed_block_spmv_pallas(
            xb,
            c.block_first,
            c.deltas,
            c.valid_count,
            bits,
            active,
            w,
            n=c.n,
            interpret=interpret,
            tile_blocks=tile_blocks,
        )  # (NB, B)
        if c.n_exceptions:
            fixed = _exception_block_sums(c, xb, bits, w, active)  # (NE, B)
            per_block = per_block.at[c.exc_block].set(fixed)
    return jax.ops.segment_sum(per_block, c.block_src, num_segments=c.n + 1)[: c.n].T
