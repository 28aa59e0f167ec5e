"""Pallas TPU kernel: fused delta-decode + blocked masked SpMV — the dense
edgeMap hot loop over the **compressed** graph backend (§5.1.3, App. D.1).

PSAM → TPU mapping: the vertex state ``x`` (small memory) is VMEM-resident
across the whole grid; the *compressed* edge blocks — one int32 first-target
plus uint16 deltas per block — are streamed HBM→VMEM at roughly half the
bytes of the int32 target stream the uncompressed kernel reads.  The decode
(a lane-dimension prefix sum) happens in VMEM, fused with the gather and the
masked reduction: the int32 targets are never materialized in HBM, which is
the TPU analogue of the paper's "decode the whole block to fetch one edge"
filter-iterator discipline.  The graphFilter bits ride along as one uint32
word per 32 edges, exactly as in ``edge_block_spmv``.

Filtered traversals stream a *second* packed bitmask, ``edge_active`` — the
per-call traversal mask (spanner's intra-cluster edges, biconnectivity's
non-tree edges, a graphFilter snapshot) — as its own aligned (TB, F_B/32)
uint32 tile per program.  Both masks are unpacked with vector shifts and
ANDed into the validity mask *in-kernel*, so a filtered edgeMap never
round-trips a combined mask (or worse, decoded targets) through HBM.

Exception handling: deltas ≥ 2¹⁶ are stored as the ESCAPE sentinel and the
kernel decodes those blocks *incorrectly on purpose* — patching a COO
exception list inside a tiled kernel would serialize the pipeline.  The
(rare) exception blocks are recomputed exactly by the wrapper in ops.py
(with the same edge_active masking) and overwritten in the per-block
output; see ``compressed_spmv_vertex``.

Grid: one program per tile of TB edge-blocks, mirroring edge_block_spmv.

Query batching (the serving subsystem's amortization lever): ``x`` may carry
a leading query dimension, ``(B, n_pad)``.  The compressed tile — first
targets, uint16 deltas, valid counts, both packed bitmasks and the optional
weight tile — is streamed into VMEM **once per grid step** and the fused
delta decode runs once; only the gather and masked reduction fan out across
the B vertex-state columns.  The compressed edge-byte reads (the scarce
NVRAM resource) are thus paid once per sweep instead of once per query.
Output grows a trailing query axis: ``(NB, B)``.

Chunked (frontier-sparse) mode: ``compressed_chunked_spmv_pallas`` is the
EDGEMAPCHUNKED analogue of the dense grid above.  Instead of walking every
block, the grid is driven by ``pltpu.PrefetchScalarGridSpec`` whose
scalar-prefetched operand is the *compacted live block-id list* (the
``compact_mask`` of frontier-owned blocks): every BlockSpec ``index_map``
indexes through it (``lambda i, ids: (ids[i], 0)``), so only live delta /
bitmask / weight tiles move HBM→VMEM.  One launch covers one chunk of
``TB`` ids; the caller's chunk loop sizes the launch count to
``ceil(k / TB)`` (k = live blocks), and out-of-range ids (the pad of the
last chunk) land on an all-sentinel row appended behind the real blocks —
streamed bytes are proportional to the live blocks, never to NB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...tuning.defaults import DEFAULT_TILE_BLOCKS  # TB: edge-blocks per program
from ..lowering import resolve_interpret


def _decode_tile(first, deltas, vc):
    """Fused block decode of one tile, in ops Mosaic lowers.

    ``first`` and ``vc`` are (TB, 1) int32 columns (first target, valid
    slot count), ``deltas`` the (TB, FB) uint16 tile.  Returns the decoded
    int32 targets, the lane iota and the structural padding mask.  The lane
    prefix sum is log2(FB) shifted adds (``pltpu.roll``) in int32, so it is
    exact and equal to ``jnp.cumsum``, which Mosaic cannot lower.
    """
    d = deltas.astype(jnp.int32)
    lane = lax.broadcasted_iota(jnp.int32, d.shape, 1)
    d = jnp.where(lane == 0, 0, d)  # lane 0's delta slot is unused
    shift = 1
    while shift < d.shape[1]:
        d = d + jnp.where(lane >= shift, pltpu.roll(d, shift, 1), 0)
        shift *= 2
    return first + d, lane, lane < vc


def _lane_bits(words, lane):
    """(TB, FB/32) packed uint32 words → (TB, FB) bool, in the canonical
    little-endian order of ``graph_filter.unpack_word_bits``.  Each lane
    selects its word by comparison instead of the 3-D reshape Mosaic
    refuses."""
    word = jnp.zeros(lane.shape, jnp.uint32)
    for w in range(words.shape[1]):
        word = jnp.where(lane // 32 == w, words[:, w : w + 1], word)
    return ((word >> (lane & 31).astype(jnp.uint32)) & jnp.uint32(1)) != 0


def _kernel(
    x_ref,
    first_ref,
    deltas_ref,
    vc_ref,
    bits_ref,
    *rest,
    n: int,
    has_active: bool,
    has_weights: bool,
    batched: bool,
):
    refs = list(rest)
    out_ref = refs.pop()
    act_ref = refs.pop(0) if has_active else None  # rides right after bits
    w_ref = refs.pop(0) if has_weights else None
    x = x_ref[...]                # (n_pad,) or (B, n_pad) — PSAM small memory
    # fused decode of the (TB, FB) uint16 tile against the (TB, 1) first
    # targets / valid counts — decoded ONCE per tile whatever the batch width
    dst, lane, mask = _decode_tile(first_ref[...], deltas_ref[...], vc_ref[...])
    mask = mask & _lane_bits(bits_ref[...], lane)  # graphFilter view
    if act_ref is not None:
        # per-call traversal mask: same packed layout, ANDed in VMEM
        mask = mask & _lane_bits(act_ref[...], lane)
    safe = jnp.where(mask & (dst < jnp.int32(n)), dst, 0)
    if batched:
        # one compressed tile, B query columns: gather fans the decoded
        # targets across the batch; the delta stream was read exactly once
        xv = jnp.take(x, safe.reshape(-1), axis=1).reshape(
            x.shape[0], *safe.shape
        )                         # (B, TB, FB)
        if w_ref is not None:
            xv = xv * w_ref[...][None]
        contrib = jnp.where(mask[None], xv, jnp.zeros((), x.dtype))
        out_ref[...] = jnp.sum(contrib, axis=2).T  # (TB, B)
        return
    xv = x[safe]                  # gather from VMEM-resident vertex state
    if w_ref is not None:
        # weights don't delta-compress (§5.1.3): they stream uncompressed as
        # a (TB, FB) tile aligned slot-for-slot with the decoded targets
        xv = xv * w_ref[...]
    contrib = jnp.where(mask, xv, jnp.zeros((), x.dtype))
    out_ref[...] = jnp.sum(contrib, axis=1)


@functools.partial(jax.jit, static_argnames=("n", "tile_blocks", "interpret"))
def compressed_block_spmv_pallas(
    x: jnp.ndarray,            # (n_pad,) vertex values, or (B, n_pad) batch
    block_first: jnp.ndarray,  # (NB,) int32
    deltas: jnp.ndarray,       # (NB, FB) uint16
    valid_count: jnp.ndarray,  # (NB,) uint16/int32 — real slots per block
    bits: jnp.ndarray,         # (NB, FB//32) uint32
    edge_active: jnp.ndarray | None = None,    # (NB, FB//32) uint32, packed
    block_weights: jnp.ndarray | None = None,  # (NB, FB) f32, uncompressed
    *,
    n: int,
    tile_blocks: int = DEFAULT_TILE_BLOCKS,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Per-block partial sums off the compressed stream:
    out[b] = Σ_slot active(b,slot)·w(b,slot)·x[decode(b)[slot]].

    ``edge_active`` (optional) is the packed per-call traversal mask, one
    uint32 word per 32 edge slots in the same block-aligned layout as the
    graphFilter ``bits``; it streams as its own (TB, F_B/32) tile and is
    ANDed into the validity mask in-kernel.  ``block_weights`` (optional) is
    the parallel *uncompressed* weight stream: weights don't
    difference-encode, so they ride as a plain (TB, FB) VMEM tile per
    program, aligned slot-for-slot with the decoded targets.  Blocks
    containing ESCAPE deltas decode wrong here and must be patched by the
    caller (ops.compressed_spmv_vertex does this).

    Batched queries: ``x`` of shape (B, n_pad) returns (NB, B) — each grid
    step streams the compressed tile and decodes it once, then applies it
    to all B columns.

    ``interpret=None`` (the default) resolves the Pallas lowering per
    backend — native Mosaic on TPU, interpret mode elsewhere
    (:mod:`repro.kernels.lowering`).
    """
    interpret = resolve_interpret(interpret, kernel="compressed_block_spmv")
    batched = x.ndim == 2
    NB, FB = deltas.shape
    block_first = block_first.reshape(NB, 1)
    vc = valid_count.astype(jnp.int32).reshape(NB, 1)
    TB = min(tile_blocks, NB)
    pad = (-NB) % TB
    if pad:
        block_first = jnp.pad(block_first, ((0, pad), (0, 0)), constant_values=n)
        deltas = jnp.pad(deltas, ((0, pad), (0, 0)))
        vc = jnp.pad(vc, ((0, pad), (0, 0)))
        bits = jnp.pad(bits, ((0, pad), (0, 0)))
        if edge_active is not None:
            edge_active = jnp.pad(edge_active, ((0, pad), (0, 0)))
        if block_weights is not None:
            block_weights = jnp.pad(block_weights, ((0, pad), (0, 0)))
    nb_pad = NB + pad
    grid = (nb_pad // TB,)
    W = FB // 32

    x_spec = (
        pl.BlockSpec(x.shape, lambda i: (0, 0))       # (B, n_pad) resident
        if batched
        else pl.BlockSpec((x.shape[0],), lambda i: (0,))  # x stays resident
    )
    in_specs = [
        x_spec,
        pl.BlockSpec((TB, 1), lambda i: (i, 0)),      # compressed stream:
        pl.BlockSpec((TB, FB), lambda i: (i, 0)),     #   first + deltas
        pl.BlockSpec((TB, 1), lambda i: (i, 0)),      #   + valid counts
        pl.BlockSpec((TB, W), lambda i: (i, 0)),
    ]
    operands = [x, block_first, deltas, vc, bits]
    if edge_active is not None:
        in_specs.append(pl.BlockSpec((TB, W), lambda i: (i, 0)))
        operands.append(edge_active)
    if block_weights is not None:
        in_specs.append(pl.BlockSpec((TB, FB), lambda i: (i, 0)))
        operands.append(block_weights)

    if batched:
        out_specs = pl.BlockSpec((TB, x.shape[0]), lambda i: (i, 0))
        out_shape = jax.ShapeDtypeStruct((nb_pad, x.shape[0]), x.dtype)
    else:
        out_specs = pl.BlockSpec((TB,), lambda i: (i,))
        out_shape = jax.ShapeDtypeStruct((nb_pad,), x.dtype)

    out = pl.pallas_call(
        functools.partial(
            _kernel,
            n=n,
            has_active=edge_active is not None,
            has_weights=block_weights is not None,
            batched=batched,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)
    return out[:NB]


def _chunked_kernel(
    ids_ref,
    *refs,
    n: int,
    emit: str,
    has_x: bool,
    has_bits: bool,
    has_active: bool,
    has_weights: bool,
    batched: bool,
):
    """One live block per program.  ``ids_ref`` is the scalar-prefetched
    compacted block-id list — the BlockSpec index_maps have already steered
    this program's delta/bitmask/weight tiles to row ``ids[i]``, so the body
    is the same fused decode as ``_kernel``, minus any knowledge of NB."""
    del ids_ref  # consumed entirely by the index_maps
    refs = list(refs)
    x_ref = refs.pop(0) if has_x else None
    first_ref = refs.pop(0)
    deltas_ref = refs.pop(0)
    vc_ref = refs.pop(0)
    bits_ref = refs.pop(0) if has_bits else None
    act_ref = refs.pop(0) if has_active else None
    w_ref = refs.pop(0) if has_weights else None

    # (rows, 1) first targets / valid counts, (rows, FB) uint16 deltas
    dst, lane, mask = _decode_tile(first_ref[...], deltas_ref[...], vc_ref[...])
    if bits_ref is not None:
        mask = mask & _lane_bits(bits_ref[...], lane)
    if act_ref is not None:
        mask = mask & _lane_bits(act_ref[...], lane)

    if emit == "decode":
        dst_out_ref, w_out_ref = refs
        dst_out_ref[...] = jnp.where(mask & (dst < jnp.int32(n)), dst, jnp.int32(n))
        w_out_ref[...] = (
            w_ref[...] if w_ref is not None else jnp.ones(dst.shape, jnp.float32)
        )
        return

    out_ref = refs[-1]
    x = x_ref[...]
    safe = jnp.where(mask & (dst < jnp.int32(n)), dst, 0)
    if batched:
        xv = jnp.take(x, safe.reshape(-1), axis=1).reshape(
            x.shape[0], *safe.shape
        )                         # (B, 1, FB)
        if w_ref is not None:
            xv = xv * w_ref[...][None]
        contrib = jnp.where(mask[None], xv, jnp.zeros((), x.dtype))
        out_ref[...] = jnp.sum(contrib, axis=2).T  # (1, B)
        return
    xv = x[safe]
    if w_ref is not None:
        xv = xv * w_ref[...]
    contrib = jnp.where(mask, xv, jnp.zeros((), x.dtype))
    out_ref[...] = jnp.sum(contrib, axis=1)


@functools.partial(
    jax.jit,
    static_argnames=("n", "emit", "interpret", "gather_tiles", "tile_blocks"),
)
def compressed_chunked_spmv_pallas(
    x: jnp.ndarray | None,         # (n_pad,) / (B, n_pad) for "sums"; None for "decode"
    ids: jnp.ndarray,              # (C,) int32 — compacted live block ids (pad: >= NB)
    block_first: jnp.ndarray,      # (NB,) int32
    deltas: jnp.ndarray,           # (NB, FB) uint16
    valid_count: jnp.ndarray,      # (NB,) uint16/int32
    bits: jnp.ndarray | None = None,          # (NB, FB//32) uint32 graphFilter
    edge_active: jnp.ndarray | None = None,   # (NB, FB//32) uint32 traversal mask
    block_weights: jnp.ndarray | None = None,  # (NB, FB) f32, uncompressed
    *,
    n: int,
    emit: str = "sums",
    interpret: bool | None = None,
    gather_tiles: bool = True,
    tile_blocks: int = DEFAULT_TILE_BLOCKS,
):
    """Frontier-sparse chunked mode: stream ONLY the blocks named by ``ids``.

    The grid is one program per entry of ``ids`` (one chunk of a compacted
    live-block list, ``compact_mask`` of the frontier-owned blocks) under a
    ``pltpu.PrefetchScalarGridSpec``: ``ids`` is the scalar-prefetched
    operand and every edge-side BlockSpec indexes through it
    (``lambda i, ids: (ids[i], 0)``), so the delta / bitmask / weight tiles
    of dead blocks are never moved HBM→VMEM.  Ids ≥ NB (the ``compact_mask``
    fill of the last chunk's pad) are clamped onto an all-sentinel row
    appended behind the real blocks: ``valid_count`` 0, first target ``n`` —
    it decodes to nothing, in either emit mode.

    ``emit``:

    * ``"sums"``   — per-live-block partial SpMV sums, ``(C,)`` (or ``(C, B)``
      when ``x`` is a ``(B, n_pad)`` query batch: the tile streams and
      decodes once, the gather fans across B — the serving amortization,
      chunked).
    * ``"decode"`` — the chunk pool of EDGEMAPCHUNKED: masked decoded
      targets ``(C, FB)`` int32 (inactive slots = sentinel ``n``) plus the
      aligned weight tile ``(C, FB)`` f32.  This is the variant the core
      ``edgemap_chunked`` streamed path consumes — decode in-kernel, monoid
      scatter outside, peak intermediate C × F_B small-memory words.

    Exception blocks (ESCAPE deltas) decode wrong here, exactly as in the
    dense-grid kernel; the wrapper patches them keyed on the gathered ids
    (``ops._patch_exception_tile`` / the per-block sum fixup).

    Tiling (``gather_tiles``, the default): BlockSpec index_maps are
    block-granular, so the id-steered grid above can only fetch ``(1, FB)``
    rows — DMA-granularity-pessimal.  The tiled mode instead pre-gathers
    the live rows (an XLA gather of exactly the ``ids`` rows — the NVRAM
    reads are unchanged) into contiguous ``(C, FB)`` buffers and runs a
    plain ``(ceil(C/TB),)`` grid of ``(TB, FB)`` tiles: each HBM→VMEM
    transfer is TB rows wide and the grid pipeline double-buffers tile
    ``i+1``'s DMA against tile ``i``'s decode.  ``gather_tiles=False``
    keeps the row-steered ``PrefetchScalarGridSpec`` grid (the
    microbenchmark baseline).  Emit shapes are identical either way.

    ``interpret=None`` resolves the lowering per backend — native Mosaic
    on TPU, interpret elsewhere (:mod:`repro.kernels.lowering`).
    """
    if emit not in ("sums", "decode"):
        raise ValueError(f"emit must be 'sums' or 'decode', got {emit!r}")
    interpret = resolve_interpret(
        interpret,
        kernel=(
            "compressed_chunked_decode"
            if emit == "decode" and gather_tiles
            else f"compressed_chunked_{emit}" + ("" if gather_tiles else "_rows")
        ),
    )
    NB, FB = deltas.shape
    C = ids.shape[0]
    W = FB // 32
    batched = emit == "sums" and x.ndim == 2

    ids = jnp.minimum(ids.astype(jnp.int32), jnp.int32(NB))
    if gather_tiles:
        return _chunked_tiled_call(
            x, ids, block_first, deltas, valid_count, bits, edge_active,
            block_weights, n=n, emit=emit, batched=batched, C=C, NB=NB, FB=FB,
            W=W, tile_blocks=tile_blocks, interpret=interpret,
        )

    # the all-sentinel row: out-of-range ids (chunk pad) land here and
    # decode to nothing (valid_count 0; first target = n for belt-and-braces)
    first_s = jnp.pad(block_first, (0, 1), constant_values=n).reshape(NB + 1, 1)
    deltas_s = jnp.pad(deltas, ((0, 1), (0, 0)))
    vc_s = jnp.pad(valid_count.astype(jnp.int32), (0, 1)).reshape(NB + 1, 1)

    in_specs = []
    operands = []
    if emit == "sums":
        in_specs.append(
            pl.BlockSpec(x.shape, lambda i, ids: (0, 0))
            if batched
            else pl.BlockSpec((x.shape[0],), lambda i, ids: (0,))
        )
        operands.append(x)
    in_specs += [
        pl.BlockSpec((1, 1), lambda i, ids: (ids[i], 0)),   # first targets
        pl.BlockSpec((1, FB), lambda i, ids: (ids[i], 0)),  # delta stream
        pl.BlockSpec((1, 1), lambda i, ids: (ids[i], 0)),   # valid counts
    ]
    operands += [first_s, deltas_s, vc_s]
    if bits is not None:
        in_specs.append(pl.BlockSpec((1, W), lambda i, ids: (ids[i], 0)))
        operands.append(jnp.pad(bits, ((0, 1), (0, 0))))
    if edge_active is not None:
        in_specs.append(pl.BlockSpec((1, W), lambda i, ids: (ids[i], 0)))
        operands.append(jnp.pad(edge_active, ((0, 1), (0, 0))))
    if block_weights is not None:
        in_specs.append(pl.BlockSpec((1, FB), lambda i, ids: (ids[i], 0)))
        operands.append(jnp.pad(block_weights, ((0, 1), (0, 0))))

    if emit == "decode":
        out_specs = (
            pl.BlockSpec((1, FB), lambda i, ids: (i, 0)),
            pl.BlockSpec((1, FB), lambda i, ids: (i, 0)),
        )
        out_shape = (
            jax.ShapeDtypeStruct((C, FB), jnp.int32),
            jax.ShapeDtypeStruct((C, FB), jnp.float32),
        )
    elif batched:
        out_specs = pl.BlockSpec((1, x.shape[0]), lambda i, ids: (i, 0))
        out_shape = jax.ShapeDtypeStruct((C, x.shape[0]), x.dtype)
    else:
        out_specs = pl.BlockSpec((1,), lambda i, ids: (i,))
        out_shape = jax.ShapeDtypeStruct((C,), x.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(C,),
        in_specs=in_specs,
        out_specs=out_specs,
    )
    return pl.pallas_call(
        functools.partial(
            _chunked_kernel,
            n=n,
            emit=emit,
            has_x=emit == "sums",
            has_bits=bits is not None,
            has_active=edge_active is not None,
            has_weights=block_weights is not None,
            batched=batched,
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(ids, *operands)


def _chunked_tiled_call(
    x, ids, block_first, deltas, valid_count, bits, edge_active, block_weights,
    *, n, emit, batched, C, NB, FB, W, tile_blocks, interpret,
):
    """The ``gather_tiles`` grid: pre-gathered live rows, (TB, FB) tiles.

    ``ids`` is already clamped to NB, the id of the all-sentinel row, which
    the gather fills in (valid count 0, first target ``n``) rather than
    copying a padded compressed array; the pad extending C to a TB multiple
    names more such rows, and they decode to nothing.  The gather reads
    exactly the live rows out of the compressed arrays; the kernel then
    walks contiguous (TB, FB)
    tiles, so every HBM→VMEM transfer is DMA-sized and the grid pipeline
    overlaps tile i+1's fetch with tile i's decode.
    """
    TB = max(1, min(tile_blocks, C))
    pad = (-C) % TB
    if pad:
        ids = jnp.pad(ids, (0, pad), constant_values=NB)
    c_pad = C + pad

    def rows(a, fill):
        # live rows only; ids == NB fill as the all-sentinel row
        return jnp.take(a, ids, axis=0, mode="fill", fill_value=fill)

    first_g = rows(block_first, n).reshape(c_pad, 1)
    deltas_g = rows(deltas, 0)                             # (C_pad, FB)
    vc_g = rows(valid_count.astype(jnp.int32), 0).reshape(c_pad, 1)

    in_specs = []
    operands = []
    if emit == "sums":
        in_specs.append(
            pl.BlockSpec(x.shape, lambda i: (0, 0))
            if batched
            else pl.BlockSpec((x.shape[0],), lambda i: (0,))
        )
        operands.append(x)
    in_specs += [
        pl.BlockSpec((TB, 1), lambda i: (i, 0)),
        pl.BlockSpec((TB, FB), lambda i: (i, 0)),
        pl.BlockSpec((TB, 1), lambda i: (i, 0)),
    ]
    operands += [first_g, deltas_g, vc_g]
    if bits is not None:
        in_specs.append(pl.BlockSpec((TB, W), lambda i: (i, 0)))
        operands.append(rows(bits, 0))
    if edge_active is not None:
        in_specs.append(pl.BlockSpec((TB, W), lambda i: (i, 0)))
        operands.append(rows(edge_active, 0))
    if block_weights is not None:
        in_specs.append(pl.BlockSpec((TB, FB), lambda i: (i, 0)))
        operands.append(rows(block_weights, 0.0))

    if emit == "decode":
        out_specs = (
            pl.BlockSpec((TB, FB), lambda i: (i, 0)),
            pl.BlockSpec((TB, FB), lambda i: (i, 0)),
        )
        out_shape = (
            jax.ShapeDtypeStruct((c_pad, FB), jnp.int32),
            jax.ShapeDtypeStruct((c_pad, FB), jnp.float32),
        )
    elif batched:
        out_specs = pl.BlockSpec((TB, x.shape[0]), lambda i: (i, 0))
        out_shape = jax.ShapeDtypeStruct((c_pad, x.shape[0]), x.dtype)
    else:
        out_specs = pl.BlockSpec((TB,), lambda i: (i,))
        out_shape = jax.ShapeDtypeStruct((c_pad,), x.dtype)

    out = pl.pallas_call(
        functools.partial(
            _chunked_kernel,
            None,  # no scalar-prefetch operand on the plain grid
            n=n,
            emit=emit,
            has_x=emit == "sums",
            has_bits=bits is not None,
            has_active=edge_active is not None,
            has_weights=block_weights is not None,
            batched=batched,
        ),
        grid=(c_pad // TB,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)
    if emit == "decode":
        return out[0][:C], out[1][:C]
    return out[:C]
