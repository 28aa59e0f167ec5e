"""Pallas TPU kernel: blocked-CSR masked SpMV — the dense edgeMap hot loop.

PSAM → TPU mapping: the vertex state ``x`` (small memory) is VMEM-resident
across the whole grid; the edge blocks (large memory) are streamed
HBM→VMEM tile by tile and *never written*.  The graphFilter bits ride along
as one uint32 word per 32 edges and are unpacked with vector shifts —
the TPU-idiomatic equivalent of the paper's TZCNT/BLSR word loop (§4.2.3).
Filtered traversals stream a second packed bitmask (``edge_active``, the
per-call traversal mask) as its own aligned (TB, F_B/32) tile; both masks
are ANDed into the validity mask in-kernel, so no combined mask is ever
materialized in HBM.

Grid: one program per tile of TB edge-blocks.  Each program produces the
per-block partial sums; the (cheap, O(#blocks)) reduction onto vertices by
``block_src`` happens outside the kernel (see ops.py) — scatter-free kernel
bodies keep the MXU/VPU pipeline free of serializing accumulations.

Query batching (the serving subsystem's amortization lever): ``x`` may carry
a leading query dimension, ``(B, n_pad)``.  The edge tile, its weights and
both packed bitmasks are loaded into VMEM **once per grid step** and applied
against all ``B`` vertex-state columns, so the NVRAM-modeled edge-byte reads
are paid once per sweep instead of once per query; only the O(B·n) vertex
state (PSAM small memory) scales with the batch.  Output grows a trailing
query axis: ``(NB, B)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.graph_filter import unpack_word_bits
from ...tuning.defaults import DEFAULT_TILE_BLOCKS  # TB: edge-blocks per program
from ..lowering import resolve_interpret


def _kernel(
    x_ref, dst_ref, w_ref, bits_ref, *rest, n: int, has_active: bool, batched: bool
):
    refs = list(rest)
    out_ref = refs.pop()
    dst = dst_ref[...]            # (TB, FB) int32 — streamed edge block tile
    w = w_ref[...]                # (TB, FB)
    x = x_ref[...]                # (n_pad,) or (B, n_pad) — PSAM small memory
    bits = bits_ref[...]          # (TB, FB//32) uint32 — graphFilter view

    act = unpack_word_bits(bits)  # (TB, FB) bool, canonical graphFilter order
    if has_active:
        act = act & unpack_word_bits(refs[0][...])  # traversal mask, in VMEM

    mask = (dst < jnp.int32(n)) & act
    safe = jnp.where(mask, dst, 0)
    if batched:
        # one edge tile, B query columns: the gather fans the (TB, FB) tile
        # out across the batch while the tile itself is loaded exactly once
        xv = jnp.take(x, safe.reshape(-1), axis=1).reshape(
            x.shape[0], *safe.shape
        )                         # (B, TB, FB)
        contrib = jnp.where(mask[None], xv * w[None], jnp.zeros((), x.dtype))
        out_ref[...] = jnp.sum(contrib, axis=2).T  # (TB, B)
    else:
        xv = x[safe]              # gather from VMEM-resident vertex state
        contrib = jnp.where(mask, xv * w, jnp.zeros((), x.dtype))
        out_ref[...] = jnp.sum(contrib, axis=1)


@functools.partial(
    jax.jit, static_argnames=("n", "tile_blocks", "interpret")
)
def edge_block_spmv_pallas(
    x: jnp.ndarray,        # (n_pad,) vertex values, or (B, n_pad) query batch
    block_dst: jnp.ndarray,  # (NB, FB) int32
    block_w: jnp.ndarray,    # (NB, FB)
    bits: jnp.ndarray,       # (NB, FB//32) uint32
    edge_active: jnp.ndarray | None = None,  # (NB, FB//32) uint32, packed
    *,
    n: int,
    tile_blocks: int = DEFAULT_TILE_BLOCKS,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Per-block partial sums: out[b] = Σ_slot active(b,slot)·w·x[dst].

    ``edge_active`` (optional) is the packed per-call traversal mask in the
    same block-aligned uint32 layout as the graphFilter ``bits``; it streams
    as its own (TB, F_B/32) tile and is ANDed in-kernel.

    Batched queries: ``x`` of shape (B, n_pad) returns (NB, B) — each grid
    step streams the edge tile once and applies it to all B columns.

    ``interpret=None`` (the default) resolves the lowering per backend —
    native Mosaic on TPU, interpret mode elsewhere."""
    interpret = resolve_interpret(interpret, kernel="edge_block_spmv")
    batched = x.ndim == 2
    NB, FB = block_dst.shape
    TB = min(tile_blocks, NB)
    pad = (-NB) % TB
    if pad:
        block_dst = jnp.pad(block_dst, ((0, pad), (0, 0)), constant_values=n)
        block_w = jnp.pad(block_w, ((0, pad), (0, 0)))
        bits = jnp.pad(bits, ((0, pad), (0, 0)))
        if edge_active is not None:
            edge_active = jnp.pad(edge_active, ((0, pad), (0, 0)))
    nb_pad = NB + pad
    grid = (nb_pad // TB,)
    W = FB // 32

    x_spec = (
        pl.BlockSpec(x.shape, lambda i: (0, 0))            # (B, n_pad) resident
        if batched
        else pl.BlockSpec((x.shape[0],), lambda i: (0,))   # x stays resident
    )
    in_specs = [
        x_spec,
        pl.BlockSpec((TB, FB), lambda i: (i, 0)),           # edge tile stream
        pl.BlockSpec((TB, FB), lambda i: (i, 0)),
        pl.BlockSpec((TB, W), lambda i: (i, 0)),
    ]
    operands = [x, block_dst, block_w, bits]
    if edge_active is not None:
        in_specs.append(pl.BlockSpec((TB, W), lambda i: (i, 0)))
        operands.append(edge_active)

    if batched:
        out_specs = pl.BlockSpec((TB, x.shape[0]), lambda i: (i, 0))
        out_shape = jax.ShapeDtypeStruct((nb_pad, x.shape[0]), x.dtype)
    else:
        out_specs = pl.BlockSpec((TB,), lambda i: (i,))
        out_shape = jax.ShapeDtypeStruct((nb_pad,), x.dtype)

    out = pl.pallas_call(
        functools.partial(
            _kernel, n=n, has_active=edge_active is not None, batched=batched
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)
    return out[:NB]
