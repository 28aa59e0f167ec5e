"""edgeMap / edgeMapChunked (§4.1) — PSAM-efficient frontier expansion.

Four execution modes, mirroring the paper:

* ``dense``  — the pass over every block (one masked segment-reduce, an
  unsorted scatter keyed by target).  A scatter costs the same per update
  whether the slot is real or sentinel padding, so the pass reads the
  blocks through the graph's degree bands (``repro.core.bands``): each
  band's blocks are row-gathered whole and packed ``F_B // w`` to a row,
  ``w`` the band's width (the power-of-two ceiling of their valid counts),
  and the bands are concatenated into one scatter.  A graph without a band
  index (the shards of a sharded plan, ``DeltaGraph``, stand-in specs)
  takes the padded pass over every slot.  Work O(m + NB); the per-slot
  targets, values and masks are materialized in HBM for the scatter, not
  fused away.
* ``sparse`` — EDGEMAPCHUNKED: only blocks owned by frontier vertices are
  touched.  The active block list is O(n) words (block size == d_avg ⇒
  #blocks = O(n), App. A), and blocks are processed in fixed-size chunks so
  the peak intermediate is ``chunk_blocks × F_B`` words — the JAX analogue of
  the paper's thread-local chunk pool (count → scan → scatter replaces
  malloc-per-thread).
* ``sparse_streamed`` — the same chunk loop, but on a ``CompressedCSR``
  backend the per-chunk tile view is produced by the frontier-sparse Pallas
  kernel (``repro.kernels.compressed_spmv``, PrefetchScalarGridSpec): the
  compacted live-id list steers the BlockSpec index_maps, so only
  frontier-owned compressed tiles move HBM→VMEM — read volume proportional
  to the live blocks, never NB, which is the PSAM sparse-round claim.
  Backends without a streaming decoder (raw ``CSRGraph``) and
  exception-dense compressed graphs fall back to plain ``sparse`` —
  identical results either way (the streamed tile is exception-patched to
  exactness).
* ``auto``   — Beamer direction optimization: dense when the frontier's
  incident-edge count exceeds ``m / dense_frac``.

Semantics (Ligra): ``out[v] = monoid over {map_fn(x[u], w_uv) : u∈frontier,
(u,v) active}``, plus a ``touched`` mask (v received ≥1 contribution).  The
caller applies the ``cond`` predicate to form the next frontier, exactly like
Ligra's C(v).

Every mode accepts either execution backend (``CSRGraph | CompressedCSR``,
see ``repro.core.backend``): the dense pass reads the backend's block view
(for compressed graphs the cumsum decode, once per call) and the chunked pass
decodes block tiles *inside* the chunk loop, so the peak intermediate stays
``chunk_blocks × F_B`` words regardless of storage format.

``edgemap_reduce_batched`` / ``edge_map_batched`` run B concurrent queries
through ONE sweep of the same bodies: the edge stream is read once per
round and fanned across the B frontier/state columns, the throughput lever
the serving subsystem (``repro.serving``) is built on.

The dense bodies trace under the named scope ``sage.sweep.dense``, the
chunked ones under ``sage.sweep.sparse``, and a compressed graph's decode
under ``sage.decode`` (``repro.obs.trace``), so a profiler trace splits a
round's device time by mode.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.metrics import get_registry
from ..obs.trace import scoped
from ..tuning.defaults import DEFAULT_CHUNK_BLOCKS, DEFAULT_DENSE_FRAC
from .backend import GraphLike, dense_block_view, tile_block_reader
from .graph_filter import GraphFilter, edge_active_words, unpack_word_bits
from .primitives import compact_mask, monoid_identity, segment_reduce
from .vertex_subset import VertexSubset


def _identity_map(x_src, w):
    del w
    return x_src


def _edge_active_view(g: GraphLike, edge_active) -> jnp.ndarray | None:
    """Normalize any edge-activity form to a bool (NB, F_B) block view.

    ``edge_active`` is planner-native: a ``GraphFilter``, packed uint32
    (NB, F_B/32) words, or a bool slot mask all mean the same thing at every
    layer (see ``repro.core.graph_filter.edge_active_words``).  Bool masks
    short-circuit (no pack/unpack round trip)."""
    if edge_active is None:
        return None
    if isinstance(edge_active, GraphFilter) or (
        hasattr(edge_active, "dtype") and edge_active.dtype == jnp.uint32
    ):
        return unpack_word_bits(edge_active_words(edge_active, g.block_size))
    return jnp.asarray(edge_active).reshape(g.num_blocks, g.block_size)


def _gather_rows(arr, idx, fill):
    return jnp.take(arr, idx, axis=0, mode="fill", fill_value=fill)


def _streaming_decoder(g: GraphLike, edge_active, interpret: bool | None = None):
    """The kernel-backed tile view for the ``sparse_streamed`` mode, or None.

    Returns ``tile(bids) -> (dst, w)`` streaming ONLY the named blocks
    HBM→VMEM (packed ``edge_active`` words folded into ``dst`` in-VMEM:
    masked slots come back as the sentinel ``n``, so the caller's
    ``dst < n`` activity test subsumes the filter).  None when the backend
    has no streaming decoder — raw ``CSRGraph`` (its block view is already
    uncompressed; the chunk gather IS the stream) or an exception-dense
    ``CompressedCSR`` (the COO patch would stop being a rare path).

    ``interpret`` is the Pallas lowering decision threaded down from the
    plan (``None`` → resolve per backend, ``repro.kernels.lowering``)."""
    from .compressed import CompressedCSR, exception_dense

    if not isinstance(g, CompressedCSR) or exception_dense(g):
        return None
    # lazy import: kernels depend on core, never the other way around
    from ..kernels.compressed_spmv.ops import (
        _exception_row_targets,
        compressed_chunked_stream_tile,
    )

    if edge_active is None:
        words = None
    elif isinstance(edge_active, GraphFilter) or (
        hasattr(edge_active, "dtype") and edge_active.dtype == jnp.uint32
    ):
        words = edge_active_words(edge_active, g.block_size)
    else:  # bool-ish slot mask, flat or (NB, F_B) — pack to canonical words
        words = edge_active_words(jnp.asarray(edge_active).astype(bool), g.block_size)

    # exception rows are id-independent: decode them exactly ONCE here, so
    # the chunk loop's per-iteration patch is a cheap match + scatter (the
    # O(NE·F_B) exact decode becomes a hoisted loop input, not loop body)
    with jax.named_scope("sage.decode"):
        exact = _exception_row_targets(g, words) if g.n_exceptions else None

    @scoped("sage.decode")
    def tile(bids):
        return compressed_chunked_stream_tile(
            g, bids, words, exact_rows=exact, interpret=interpret
        )

    return tile


def _combine(monoid, a, b):
    if monoid == "sum":
        return a + b
    if monoid == "min":
        return jnp.minimum(a, b)
    if monoid == "max":
        return jnp.maximum(a, b)
    if monoid == "or":
        return a | b
    raise ValueError(monoid)


def _dense_routing(g: GraphLike):
    """The dense sweep's row groups ``[(ids, owners, width)]``, in order.

    With a band index (``repro.core.bands``) the groups are its bands:
    ``ids`` a band's block ids and ``owners`` theirs, both padded with
    out-of-range fill to whole packed rows (``_pack_rows``) of 8-row tiles,
    and ``width`` the lanes read of each block.  Without one (shards of a
    sharded plan, ``DeltaGraph``, stand-in specs) it is one group of every
    block at full width, in storage order (``ids`` None): the padded sweep.
    The slots the groups hold are recorded, at trace time, in the gauge
    ``sage_dense_sweep_slots{kind=scattered|padded}``."""
    bands = getattr(g, "bands", None)
    fb = g.block_size
    if bands is None:
        groups = [(None, g.block_src, fb)]
    else:
        groups = []
        for w, s, r in bands.spans():
            tile = 8 * (fb // w)
            pad = -r % tile
            ids = jnp.pad(bands.block_ids[s : s + r], (0, pad), constant_values=g.num_blocks)
            owners = jnp.pad(bands.owners[s : s + r], (0, pad), constant_values=g.n)
            groups.append((ids, owners, w))
    gauge = get_registry().gauge(
        "sage_dense_sweep_slots",
        "slots the last traced dense sweep scatters, and the padded slots of its graph",
        labels=("kind",),
    )
    scattered = sum(own.shape[0] // (fb // w) * fb for _, own, w in groups)
    gauge.set(scattered, kind="scattered")
    gauge.set(g.num_blocks * fb, kind="padded")
    return groups


def _cat(parts, axis=0):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=axis)


def _lane_segment(fb: int, w: int, axis: int, ndim: int):
    """Each lane's segment ``lane // w``, shaped to broadcast along ``axis``
    of an ``ndim``-dimensional array."""
    seg = jnp.arange(fb, dtype=jnp.int32) // w
    return seg.reshape((1,) * axis + (fb,) + (1,) * (ndim - axis - 1))


def _pack_rows(rows, w: int):
    """Pack a band's ``(k·g, F_B)`` rows, ``k = F_B // w``, into ``(g, F_B)``:
    lanes ``[j·w, (j+1)·w)`` of row r hold the first w lanes of row
    ``j·g + r``.  Every array keeps F_B lanes, so no narrow ``(R, w)``
    array is laid out (padded to the full lane width) on the way to the
    scatter; a lane rotation does the packing."""
    fb = rows.shape[1]
    k = fb // w
    if k == 1:
        return rows
    parts = rows.reshape((k, rows.shape[0] // k) + rows.shape[1:])
    seg = _lane_segment(fb, w, 1, rows.ndim)
    out = parts[0]
    for j in range(1, k):
        out = jnp.where(seg == j, jnp.roll(parts[j], j * w, axis=1), out)
    return out


def _pack_blocks(v, w: int, fb: int, axis: int = 0):
    """Per-block values, blocks along ``axis``, laid out as ``_pack_rows``
    lays out their rows: ``axis`` becomes ``(g, F_B)``."""
    k = fb // w
    g = v.shape[axis] // k
    seg = _lane_segment(fb, w, axis + 1, v.ndim + 1)
    out = None
    for j in range(k):
        part = jnp.expand_dims(lax.slice_in_dim(v, j * g, (j + 1) * g, axis=axis), axis + 1)
        out = part if out is None else jnp.where(seg == j, part, out)
    return jnp.broadcast_to(out, v.shape[:axis] + (g, fb) + v.shape[axis + 1 :])


def _slot_rows(arr, groups, fill):
    """A (NB, F_B) block array as flat slots in routing order."""
    return _cat([
        _pack_rows(
            arr if ids is None else jnp.take(arr, ids, axis=0, mode="fill", fill_value=fill), w
        ).reshape(-1)
        for ids, _, w in groups
    ])


@scoped("sage.sweep.dense")
def edgemap_dense(
    g: GraphLike,
    frontier_mask: jnp.ndarray,
    x: jnp.ndarray,
    *,
    monoid: str = "min",
    map_fn: Callable = _identity_map,
    edge_active: jnp.ndarray | None = None,
    interpret: bool | None = None,
):
    """Dense pass over every block's slots.  Returns (out[n,...], touched[n]).

    Reads the backend's block view (for ``CompressedCSR`` the decoded
    targets, once per call) through the degree bands of ``_dense_routing``
    and runs one segment-reduce over the slots read.

    ``interpret`` is accepted for call-site symmetry with the chunked /
    streamed paths but is a no-op here: this body is pure jnp, there is no
    Pallas kernel to steer.
    """
    del interpret
    n, fb = g.n, g.block_size
    feat = x.shape[1:]
    ident = monoid_identity(monoid, x.dtype)
    groups = _dense_routing(g)
    block_dst, block_w = dense_block_view(g)

    def spread(v, fill):  # per-vertex values at each group's owners -> slots
        return _cat([
            _pack_blocks(_gather_rows(v, own, fill), w, fb).reshape((-1,) + v.shape[1:])
            for _, own, w in groups
        ])

    edge_dst = _slot_rows(block_dst, groups, n)
    act = spread(frontier_mask, False) & (edge_dst < jnp.int32(n))
    ea = _edge_active_view(g, edge_active)
    if ea is not None:
        act = act & _slot_rows(ea, groups, False)
    xs = spread(x, ident)
    edge_w = _slot_rows(block_w, groups, 0.0)
    w = edge_w if not feat else edge_w[..., None]
    vals = map_fn(xs, w)
    if vals.ndim > act.ndim:
        sel = act.reshape(act.shape + (1,) * (vals.ndim - act.ndim))
    else:
        sel = act
    vals = jnp.where(sel, vals, ident)
    ids = jnp.where(act, edge_dst, jnp.int32(n))
    out = segment_reduce(vals, ids, n + 1, monoid)[:n]
    touched = (
        jax.ops.segment_max(act.astype(jnp.int32), ids, num_segments=n + 1)[:n] > 0
    )
    return out, touched


@scoped("sage.sweep.sparse")
def edgemap_chunked(
    g: GraphLike,
    frontier_mask: jnp.ndarray,
    x: jnp.ndarray,
    *,
    monoid: str = "min",
    map_fn: Callable = _identity_map,
    edge_active: jnp.ndarray | None = None,
    chunk_blocks: int = DEFAULT_CHUNK_BLOCKS,
    streamed: bool = False,
    interpret: bool | None = None,
):
    """EDGEMAPCHUNKED — only frontier-owned blocks, chunked emission.

    With ``streamed=True`` (the ``sparse_streamed`` mode) a ``CompressedCSR``
    backend swaps the per-chunk jnp decode for the frontier-sparse Pallas
    kernel: the compacted live-id chunk is the kernel's scalar-prefetched
    operand, and only those blocks' compressed tiles stream HBM→VMEM —
    ``ceil(k / chunk_blocks)`` launches of ``chunk_blocks`` blocks each, so
    streamed bytes track the live count ``k``, not NB.  Results are
    bit-identical to the un-streamed path (the kernel tile is
    exception-patched to exactness and the filter folding commutes with the
    activity test); backends without a streaming decoder ignore the flag.
    """
    n, NB, FB = g.n, g.num_blocks, g.block_size
    C = min(chunk_blocks, NB)
    nchunks = -(-NB // C)
    ident = monoid_identity(monoid, x.dtype)

    blk_act = _gather_rows(frontier_mask, g.block_src, False)
    idx, k = compact_mask(blk_act, fill=NB)  # O(n) words: NB = O(n) by F_B=d_avg
    idx = jnp.pad(idx, (0, nchunks * C - NB), constant_values=NB)

    feat_shape = x.shape[1:]
    out0 = jnp.full((n + 1,) + feat_shape, ident, dtype=x.dtype)
    if monoid == "or":
        out0 = jnp.zeros((n + 1,) + feat_shape, dtype=bool)
    touched0 = jnp.zeros(n + 1, dtype=jnp.int32)

    stream_tile = _streaming_decoder(g, edge_active, interpret) if streamed else None
    bits = _edge_active_view(g, edge_active) if stream_tile is None else None
    read_tile = tile_block_reader(g) if stream_tile is None else None

    def body(state):
        i, out, touched = state
        bids = lax.dynamic_slice(idx, (i * C,), (C,))
        if stream_tile is not None:
            # Pallas frontier-sparse decode: ONLY these C blocks' compressed
            # tiles move; filter bits already folded (masked slots → n)
            dsts, ws = stream_tile(bids)                   # (C, FB)
            act = dsts < n
        else:
            # per-backend tile view; compressed backends decode here, inside
            # the chunk loop, so the peak intermediate stays C × F_B words
            dsts, ws = read_tile(bids)                     # (C, FB)
            act = dsts < n
            if bits is not None:
                act = act & _gather_rows(bits, bids, False)
        srcs = _gather_rows(g.block_src, bids, n)          # (C,)
        xs = _gather_rows(x, srcs, ident)                  # (C, ...)
        xs = jnp.broadcast_to(xs[:, None], (C, FB) + feat_shape)
        vals = map_fn(xs, ws if not feat_shape else ws[..., None])
        sel = act if not feat_shape else act[..., None]
        vals = jnp.where(sel, vals, ident)
        ids = jnp.where(act, dsts, n).reshape(-1)
        flat = vals.reshape((C * FB,) + feat_shape)
        out = _combine(monoid, out, segment_reduce(flat, ids, n + 1, monoid))
        touched = jnp.maximum(
            touched,
            jax.ops.segment_max(act.astype(jnp.int32).reshape(-1), ids, num_segments=n + 1),
        )
        return i + 1, out, touched

    def cond(state):
        i, _, _ = state
        return (i * C < k) & (i < nchunks)

    _, out, touched = lax.while_loop(cond, body, (jnp.int32(0), out0, touched0))
    return out[:n], touched[:n] > 0


def edgemap_reduce(
    g: GraphLike,
    frontier_mask: jnp.ndarray,
    x: jnp.ndarray,
    *,
    monoid: str = "min",
    map_fn: Callable = _identity_map,
    edge_active: jnp.ndarray | None = None,
    mode: str = "auto",
    dense_frac: float | None = None,
    chunk_blocks: int | None = None,
    auto_sparse: str | None = None,
    interpret: bool | None = None,
    plan=None,
):
    """Direction-optimized edgeMap (Beamer §4.1.1).

    ``mode`` is ``'dense' | 'sparse' | 'sparse_streamed' | 'auto'`` (see the
    module docstring); ``sparse_streamed`` is ``sparse`` with the
    frontier-sparse Pallas decode on ``CompressedCSR`` backends — only live
    compressed tiles stream — and falls back to ``sparse`` elsewhere.

    With ``plan`` (an ``ExecutionPlan``, see ``repro.core.plan``) the same
    call runs wherever the plan says: a meshless plan resolves the mode /
    chunking knobs and stays on this code path; a mesh plan routes to the
    sharded executor, which runs these very bodies per shard under
    ``shard_map`` (``g`` must then be the plan-prepared ``ShardedGraph``).
    Explicit ``mode`` / ``dense_frac`` / ``chunk_blocks`` arguments win over
    the plan's.

    ``edge_active`` (GraphFilter | packed uint32 words | bool slot mask) is
    plan-native too: on a mesh plan the packed words shard block-range-wise
    alongside the edge blocks and unpack inside each shard's local body; a
    ``ShardedEdgeActive`` from ``plan.prepare(g, edge_active=...)`` skips
    the in-trace split.
    """
    if plan is not None:
        if plan.is_sharded:
            from .plan import sharded_edgemap_reduce

            return sharded_edgemap_reduce(
                plan,
                g,
                frontier_mask,
                x,
                monoid=monoid,
                map_fn=map_fn,
                edge_active=edge_active,
                mode=mode,
                dense_frac=dense_frac,
                chunk_blocks=chunk_blocks,
                auto_sparse=auto_sparse,
                interpret=interpret,
            )
        mode = plan.resolve_mode(mode)
        dense_frac = plan.dense_frac if dense_frac is None else dense_frac
        chunk_blocks = plan.chunk_blocks if chunk_blocks is None else chunk_blocks
        auto_sparse = plan.auto_sparse if auto_sparse is None else auto_sparse
        interpret = plan.interpret if interpret is None else interpret
    dense_frac = DEFAULT_DENSE_FRAC if dense_frac is None else dense_frac
    chunk_blocks = DEFAULT_CHUNK_BLOCKS if chunk_blocks is None else chunk_blocks
    auto_sparse = "sparse" if auto_sparse is None else auto_sparse
    if mode == "dense":
        return edgemap_dense(
            g, frontier_mask, x, monoid=monoid, map_fn=map_fn, edge_active=edge_active
        )
    if mode in ("sparse", "sparse_streamed"):
        return edgemap_chunked(
            g,
            frontier_mask,
            x,
            monoid=monoid,
            map_fn=map_fn,
            edge_active=edge_active,
            chunk_blocks=chunk_blocks,
            streamed=mode == "sparse_streamed",
            interpret=interpret,
        )
    sum_deg = jnp.sum(jnp.where(frontier_mask, g.degrees, 0))
    use_dense = sum_deg * dense_frac > g.m
    return lax.cond(
        use_dense,
        lambda: edgemap_dense(
            g, frontier_mask, x, monoid=monoid, map_fn=map_fn, edge_active=edge_active
        ),
        lambda: edgemap_chunked(
            g,
            frontier_mask,
            x,
            monoid=monoid,
            map_fn=map_fn,
            edge_active=edge_active,
            chunk_blocks=chunk_blocks,
            streamed=auto_sparse == "sparse_streamed",
            interpret=interpret,
        ),
    )


@scoped("sage.sweep.dense")
def edgemap_dense_batched(
    g: GraphLike,
    frontier_masks: jnp.ndarray,
    xb: jnp.ndarray,
    *,
    monoid: str = "min",
    map_fn: Callable = _identity_map,
    edge_active: jnp.ndarray | None = None,
    map_lanes: jnp.ndarray | None = None,
):
    """Dense pull pass, B queries per sweep.  Returns (out[B,n], touched[B,n]).

    The jnp analogue of the kernels' query-batch dimension: the edge-side
    work — block view (the compressed backend's fused cumsum decode
    included), validity/filter masks, and the scatter routing ``ids`` — is
    computed ONCE, and the monoid reduction runs as a single
    segment-reduce over m edge rows of B-wide value vectors, not B
    separate scatters.  Per-lane inactive slots contribute the monoid
    identity at their real target row (instead of the single-query path's
    sentinel reroute), which reduces to the same value: every lane is
    bit-identical to its own ``edgemap_dense`` run.

    ``map_lanes`` (bool[B], optional) applies ``map_fn`` only on the
    selected lanes; the rest take the identity map (``xs`` pass through
    bit-exactly).  This is what lets heterogeneous query kinds — e.g. BFS
    lanes (identity over candidate parents) and wBFS lanes (weighted
    relaxation over distances) — share ONE edge sweep while each lane runs
    its own recurrence.
    """
    n, fb, B = g.n, g.block_size, xb.shape[0]
    ident = monoid_identity(monoid, xb.dtype)
    groups = _dense_routing(g)                      # the single-query order
    block_dst, block_w = dense_block_view(g)        # shared: decoded once

    def spread(v, fill):  # (B, n) values at each group's owners -> (B, slots)
        return _cat([
            _pack_blocks(
                jnp.take(v, own, axis=1, mode="fill", fill_value=fill), w, fb, axis=1
            ).reshape(B, -1)
            for _, own, w in groups
        ], axis=1)

    edge_dst = _slot_rows(block_dst, groups, n)
    valid = edge_dst < jnp.int32(n)
    ids = jnp.where(valid, edge_dst, jnp.int32(n))  # shared scatter routing
    ea = _edge_active_view(g, edge_active)
    if ea is not None:
        valid = valid & _slot_rows(ea, groups, False)
    act = spread(frontier_masks, False) & valid[None]
    xs = spread(xb, ident)
    vals = map_fn(xs, _slot_rows(block_w, groups, 0.0)[None, :])
    if map_lanes is not None:
        vals = jnp.where(map_lanes[:, None], vals, xs)
    vals = jnp.where(act, vals, ident)
    out = segment_reduce(vals.T, ids, n + 1, monoid)[:n]          # (n, B)
    touched = (
        jax.ops.segment_max(act.T.astype(jnp.int32), ids, num_segments=n + 1)[:n]
        > 0
    )
    return out.T, touched.T


@scoped("sage.sweep.sparse")
def edgemap_chunked_batched_streamed(
    g: GraphLike,
    frontier_masks: jnp.ndarray,
    xb: jnp.ndarray,
    *,
    monoid: str = "min",
    map_fn: Callable = _identity_map,
    edge_active: jnp.ndarray | None = None,
    chunk_blocks: int = DEFAULT_CHUNK_BLOCKS,
    map_lanes: jnp.ndarray | None = None,
    interpret: bool | None = None,
):
    """Batched EDGEMAPCHUNKED over the streaming kernel: B queries, one
    compressed-tile read per live block.

    ``map_lanes`` (bool[B], optional) applies ``map_fn`` only on the
    selected lanes (the rest pass ``xs`` through bit-exactly), exactly as
    in ``edgemap_dense_batched`` — the cross-op serving rounds ride it.

    The live set is the UNION of the per-lane frontiers' blocks (any lane
    owning a block keeps it live), compacted once; each chunk is decoded by
    the frontier-sparse Pallas kernel exactly once and fanned across the B
    lanes — lanes for which a block is dead contribute the monoid identity
    at its real target rows, the same identity-contribution discipline as
    ``edgemap_dense_batched``.  Per-lane results equal the single-query
    ``edgemap_chunked(streamed=True)`` runs exactly for int/min/max/or
    state; float sums may differ in association order (allclose), exactly
    like the dense batched path's segment-reduce.  NVRAM-side reads are the
    union live blocks, once — not B times, and never NB.
    """
    n, NB, FB = g.n, g.num_blocks, g.block_size
    B = xb.shape[0]
    C = min(chunk_blocks, NB)
    nchunks = -(-NB // C)
    ident = monoid_identity(monoid, xb.dtype)

    frontier_blk = jnp.take(
        frontier_masks, g.block_src, axis=1, mode="fill", fill_value=False
    )                                                   # (B, NB)
    blk_any = jnp.any(frontier_blk, axis=0)             # union live set
    idx, k = compact_mask(blk_any, fill=NB)
    idx = jnp.pad(idx, (0, nchunks * C - NB), constant_values=NB)

    stream_tile = _streaming_decoder(g, edge_active, interpret)
    assert stream_tile is not None, "caller guards on _streaming_decoder"

    out0 = jnp.full((n + 1, B), ident, dtype=xb.dtype)
    if monoid == "or":
        out0 = jnp.zeros((n + 1, B), dtype=bool)
    touched0 = jnp.zeros((n + 1, B), dtype=jnp.int32)

    def body(state):
        i, out, touched = state
        bids = lax.dynamic_slice(idx, (i * C,), (C,))
        dsts, ws = stream_tile(bids)                    # decoded ONCE for all B
        srcs = _gather_rows(g.block_src, bids, n)       # (C,)
        act_sh = dsts < n                               # shared: filter folded
        lane_blk = jnp.take(
            frontier_masks, srcs, axis=1, mode="fill", fill_value=False
        )                                               # (B, C) — per-lane live
        xs = jnp.take(xb, srcs, axis=1, mode="fill", fill_value=ident)  # (B, C)
        xs = jnp.broadcast_to(xs[:, :, None], (B, C, FB))
        vals = map_fn(xs, ws[None])
        if map_lanes is not None:
            vals = jnp.where(map_lanes[:, None, None], vals, xs)
        act = lane_blk[:, :, None] & act_sh[None]       # (B, C, FB)
        vals = jnp.where(act, vals, ident).reshape(B, C * FB)
        ids = jnp.where(act_sh, dsts, n).reshape(-1)    # shared scatter routing
        out = _combine(monoid, out, segment_reduce(vals.T, ids, n + 1, monoid))
        touched = jnp.maximum(
            touched,
            jax.ops.segment_max(
                act.reshape(B, -1).T.astype(jnp.int32), ids, num_segments=n + 1
            ),
        )
        return i + 1, out, touched

    def cond(state):
        i, _, _ = state
        return (i * C < k) & (i < nchunks)

    _, out, touched = lax.while_loop(cond, body, (jnp.int32(0), out0, touched0))
    return out[:n].T, touched[:n].T > 0


def edgemap_reduce_batched(
    g: GraphLike,
    frontier_masks: jnp.ndarray,
    xb: jnp.ndarray,
    *,
    monoid: str = "min",
    map_fn: Callable = _identity_map,
    edge_active: jnp.ndarray | None = None,
    mode: str = "auto",
    dense_frac: float | None = None,
    chunk_blocks: int | None = None,
    auto_sparse: str | None = None,
    flavor_crossover: float | None = None,
    interpret: bool | None = None,
    plan=None,
    map_lanes: jnp.ndarray | None = None,
):
    """Batched edgeMap: B concurrent queries share ONE edge sweep.

    ``frontier_masks`` is bool[B, n], ``xb`` is [B, n] (per-query vertex
    state); returns ``(out[B, n], touched[B, n])``.  The edge blocks — the
    scarce read-only NVRAM resource in the PSAM — are streamed once per
    round and applied against all B state columns, so the edge-byte reads
    amortize ÷B (``PSAMCost.charge_edgemap_batched``) while the mutable
    state stays O(B·n) words of small memory.

    ``map_lanes`` (bool[B], optional) applies ``map_fn`` only on the
    selected lanes; unselected lanes take the identity map, bit-exactly.
    This is the cross-op batching hook: lanes running different query
    kinds (BFS candidate-parent propagation, wBFS weighted relaxation)
    share the same sweep, each with its own per-edge map — see
    ``repro.algorithms.traversal.traversal_cohort_rounds`` and the
    ``ServingService`` drain loop built on it.

    Execution: the dense strategy runs ``edgemap_dense_batched`` — one
    shared edge sweep, one m-row × B-column segment reduction.  The sparse
    strategy vmaps ``edgemap_chunked`` (per-lane active-block lists differ;
    the chunk loop masks finished lanes' carries).  ``auto`` evaluates the
    per-lane Beamer predicate and selects per lane between the two
    shared-sweep branches — exactly what a vmapped ``lax.cond`` lowers to.
    Every query's result is bit-identical to its own single-query
    ``edgemap_reduce`` run — the property the serving parity suite locks
    in.

    ``plan`` routes the batch exactly like ``edgemap_reduce``: a meshless
    plan resolves the mode/chunking knobs here; a mesh plan runs the
    batched local body per shard and monoid-combines the O(B·n) output
    (``g`` must then be the plan-prepared ``ShardedGraph``).
    """
    if plan is not None:
        if plan.is_sharded:
            from .plan import sharded_edgemap_reduce_batched

            return sharded_edgemap_reduce_batched(
                plan,
                g,
                frontier_masks,
                xb,
                monoid=monoid,
                map_fn=map_fn,
                edge_active=edge_active,
                mode=mode,
                dense_frac=dense_frac,
                chunk_blocks=chunk_blocks,
                auto_sparse=auto_sparse,
                interpret=interpret,
                map_lanes=map_lanes,
            )
        mode = plan.resolve_mode(mode)
        # batched rounds take the BATCHED knobs: their own Beamer threshold
        # (the batched dense body amortizes one shared sweep over all B
        # lanes) and their own sparse flavor (one shared live-block loop vs
        # B vmapped chunk loops) — neither crossover transfers from the
        # single-query calibration
        dense_frac = plan.dense_frac_batched if dense_frac is None else dense_frac
        chunk_blocks = plan.chunk_blocks if chunk_blocks is None else chunk_blocks
        auto_sparse = plan.auto_sparse_batched if auto_sparse is None else auto_sparse
        interpret = plan.interpret if interpret is None else interpret
        if flavor_crossover is None:
            flavor_crossover = plan.batched_flavor_crossover
    dense_frac = DEFAULT_DENSE_FRAC if dense_frac is None else dense_frac
    chunk_blocks = DEFAULT_CHUNK_BLOCKS if chunk_blocks is None else chunk_blocks
    auto_sparse = "sparse" if auto_sparse is None else auto_sparse

    def lane_map(ml):
        # per-lane map selection under vmap: ml is this lane's scalar flag,
        # so the select is a broadcast where — identity lanes pass xs
        # through bit-exactly
        if map_lanes is None:
            return map_fn
        return lambda xs, w: jnp.where(ml, map_fn(xs, w), xs)

    if xb.ndim != 2:
        # feature-dim vertex state: fall back to the vmapped bodies (the
        # streamed kernel path is not vmapped — plain sparse instead)
        vmode = "sparse" if mode == "sparse_streamed" else mode
        ml_axis = None if map_lanes is None else 0
        ml0 = jnp.zeros(xb.shape[0], bool) if map_lanes is None else map_lanes
        return jax.vmap(
            lambda fm, xv, ml: edgemap_reduce(
                g, fm, xv, monoid=monoid, map_fn=lane_map(ml),
                edge_active=edge_active,
                mode=vmode, dense_frac=dense_frac, chunk_blocks=chunk_blocks,
                interpret=interpret,
            ),
            in_axes=(0, 0, ml_axis),
        )(frontier_masks, xb, ml0)
    if mode == "dense":
        return edgemap_dense_batched(
            g, frontier_masks, xb, monoid=monoid, map_fn=map_fn,
            edge_active=edge_active, map_lanes=map_lanes,
        )

    def sparse_one(fm, xv, ml):
        return edgemap_chunked(
            g, fm, xv, monoid=monoid, map_fn=lane_map(ml),
            edge_active=edge_active, chunk_blocks=chunk_blocks,
            interpret=interpret,
        )

    ml_axis = None if map_lanes is None else 0
    ml0 = jnp.zeros(xb.shape[0], bool) if map_lanes is None else map_lanes

    # under vmap the lanes' scope reads vmap(sage.sweep.sparse): open the
    # plain scope outside it too
    @scoped("sage.sweep.sparse")
    def sparse_vmap(fm, xv):
        return jax.vmap(sparse_one, in_axes=(0, 0, ml_axis))(fm, xv, ml0)

    if mode == "sparse_streamed":
        if _streaming_decoder(g, edge_active) is not None:
            return edgemap_chunked_batched_streamed(
                g, frontier_masks, xb, monoid=monoid, map_fn=map_fn,
                edge_active=edge_active, chunk_blocks=chunk_blocks,
                map_lanes=map_lanes, interpret=interpret,
            )
        return sparse_vmap(frontier_masks, xb)
    if mode == "sparse":
        return sparse_vmap(frontier_masks, xb)
    # auto: ONE Beamer predicate for the whole batch, on the aggregate
    # density.  Per-lane selection can't win here: the batched dense body is
    # one shared sweep regardless of density, and the batched sparse body's
    # chunk loop is paced by the densest lane — so a straddling batch that
    # ran both and picked per lane (what vmap(lax.cond) lowers to) would pay
    # dense + sparse for a result bit-identical to either branch alone.  At
    # B=1 the aggregate IS the lane predicate, matching single-query auto.
    sum_deg = jnp.sum(jnp.where(frontier_masks, g.degrees[None, :], 0), axis=1)
    use_dense = jnp.sum(sum_deg) * dense_frac > frontier_masks.shape[0] * g.m

    def dense_all():
        return edgemap_dense_batched(
            g, frontier_masks, xb, monoid=monoid, map_fn=map_fn,
            edge_active=edge_active, map_lanes=map_lanes,
        )

    def sparse_all():
        # the calibrated sparse flavor: the streamed union path when the
        # table picked it AND the backend can stream, plain vmapped chunks
        # otherwise — per-lane results are bit-identical either way
        if (
            auto_sparse == "sparse_streamed"
            and _streaming_decoder(g, edge_active) is not None
        ):
            def streamed():
                return edgemap_chunked_batched_streamed(
                    g, frontier_masks, xb, monoid=monoid, map_fn=map_fn,
                    edge_active=edge_active, chunk_blocks=chunk_blocks,
                    map_lanes=map_lanes, interpret=interpret,
                )

            if flavor_crossover is None or flavor_crossover >= 1.0:
                return streamed()
            # measured flavor crossover: the shared live-block loop wins
            # only while the union frontier is sparse enough — switch to
            # the vmapped chunk loops above it, at the batch's mean lane
            # density (the quantity the calibration sweep varied)
            mean_density = jnp.sum(sum_deg) / (xb.shape[0] * g.m)
            return lax.cond(
                mean_density < flavor_crossover,
                streamed,
                lambda: sparse_vmap(frontier_masks, xb),
            )
        return sparse_vmap(frontier_masks, xb)

    return lax.cond(use_dense, dense_all, sparse_all)


def edge_map_batched(
    g: GraphLike,
    frontier_masks: jnp.ndarray,
    xb: jnp.ndarray,
    *,
    monoid: str = "min",
    map_fn: Callable = _identity_map,
    cond_masks: jnp.ndarray | None = None,
    update: str = "min",
    edge_active: jnp.ndarray | None = None,
    mode: str = "auto",
    plan=None,
    map_lanes: jnp.ndarray | None = None,
):
    """Batched Ligra-style EDGEMAP: returns (new_x[B, n], next_masks[B, n]).

    The batched analogue of ``edge_map``, with bool masks in place of
    ``VertexSubset`` (frontiers are per-query rows).  ``cond_masks[q, v]``
    plays C(v) for query q; ``update`` merges per-query contributions
    exactly as in ``edge_map``; ``map_lanes`` restricts ``map_fn`` to the
    selected lanes exactly as in ``edgemap_reduce_batched``."""
    out, touched = edgemap_reduce_batched(
        g, frontier_masks, xb, monoid=monoid, map_fn=map_fn,
        edge_active=edge_active, mode=mode, plan=plan, map_lanes=map_lanes,
    )
    ok = touched if cond_masks is None else (touched & cond_masks)
    if update == "min":
        new_x = jnp.where(ok, jnp.minimum(xb, out), xb)
        changed = ok & (out < xb)
    elif update == "max":
        new_x = jnp.where(ok, jnp.maximum(xb, out), xb)
        changed = ok & (out > xb)
    elif update == "sum":
        new_x = jnp.where(ok, xb + out, xb)
        changed = ok
    elif update == "replace":
        new_x = jnp.where(ok, out, xb)
        changed = ok
    else:
        raise ValueError(update)
    return new_x, changed


def edge_map(
    g: GraphLike,
    frontier: VertexSubset,
    x: jnp.ndarray,
    *,
    monoid: str = "min",
    map_fn: Callable = _identity_map,
    cond_mask: jnp.ndarray | None = None,
    update: str = "min",
    edge_active: jnp.ndarray | None = None,
    mode: str = "auto",
    plan=None,
):
    """Full Ligra-style EDGEMAP: returns (new_x, next_frontier).

    ``cond_mask[v]`` plays C(v); ``update`` decides how reduced contributions
    merge into x ('min'|'max'|'sum'|'replace').  ``plan`` routes execution
    (single-device or sharded) exactly as in ``edgemap_reduce``.
    """
    out, touched = edgemap_reduce(
        g, frontier.mask, x, monoid=monoid, map_fn=map_fn, edge_active=edge_active,
        mode=mode, plan=plan,
    )
    ok = touched if cond_mask is None else (touched & cond_mask)
    if update == "min":
        new_x = jnp.where(ok, jnp.minimum(x, out), x)
        changed = ok & (out < x)
    elif update == "max":
        new_x = jnp.where(ok, jnp.maximum(x, out), x)
        changed = ok & (out > x)
    elif update == "sum":
        new_x = jnp.where(ok, x + out, x)
        changed = ok
    elif update == "replace":
        new_x = jnp.where(ok, out, x)
        changed = ok
    else:
        raise ValueError(update)
    return new_x, VertexSubset(mask=changed, n=g.n)
