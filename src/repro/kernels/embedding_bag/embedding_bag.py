"""Pallas TPU kernel: EmbeddingBag (gather + weighted segment-sum).

JAX has no native EmbeddingBag; this is the recsys hot path (SASRec item
lookups, retrieval scoring).  The kernel tiles the *batch* dimension; the
embedding-table shard stays VMEM-resident across the grid (it is the
read-mostly "large" operand — at pod scale each device holds a row shard
and this kernel runs on the local shard, see distributed/shardings.py).

Bags are fixed-width (L slots) with -1 padding — the static-shape analogue
of torch's ragged offsets, produced by the data pipeline.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..lowering import resolve_interpret

DEFAULT_TILE_BATCH = 64


def _kernel(table_ref, idx_ref, wgt_ref, out_ref, *, V: int):
    idx = idx_ref[...]            # (TB, L) int32, -1 padding
    wgt = wgt_ref[...]            # (TB, L)
    table = table_ref[...]        # (V, D) — resident shard

    valid = (idx >= 0) & (idx < jnp.int32(V))
    safe = jnp.where(valid, idx, 0)
    vecs = table[safe]            # (TB, L, D) gather
    w = jnp.where(valid, wgt, jnp.zeros((), wgt.dtype))
    out_ref[...] = jnp.sum(vecs * w[..., None].astype(vecs.dtype), axis=1)


@functools.partial(jax.jit, static_argnames=("tile_batch", "interpret"))
def embedding_bag_pallas(
    table: jnp.ndarray,   # (V, D)
    indices: jnp.ndarray,  # (B, L) int32, -1 = empty slot
    weights: jnp.ndarray,  # (B, L)
    *,
    tile_batch: int = DEFAULT_TILE_BATCH,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Returns (B, D) weighted bag sums."""
    interpret = resolve_interpret(interpret, kernel="embedding_bag")
    V, D = table.shape
    B, L = indices.shape
    TB = min(tile_batch, B)
    pad = (-B) % TB
    if pad:
        indices = jnp.pad(indices, ((0, pad), (0, 0)), constant_values=-1)
        weights = jnp.pad(weights, ((0, pad), (0, 0)))
    b_pad = B + pad
    grid = (b_pad // TB,)

    out = pl.pallas_call(
        functools.partial(_kernel, V=V),
        grid=grid,
        in_specs=[
            pl.BlockSpec((V, D), lambda i: (0, 0)),   # table resident
            pl.BlockSpec((TB, L), lambda i: (i, 0)),
            pl.BlockSpec((TB, L), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((TB, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b_pad, D), table.dtype),
        interpret=interpret,
    )(table, indices, weights)
    return out[:B]
