"""Pallas TPU kernel: graphFilter PackVertex (§4.2.2) — predicate → bit
clear → popcount, one fused pass over the filter blocks.

The paper processes a block word-by-word with TZCNT/BLSR; on TPU the whole
(TB, F_B) tile is handled with vectorized shift/mask arithmetic and a
SWAR popcount — same O(q + k) word-work, lane-parallel.

All writes are to the bitset and the per-block counts (PSAM small memory);
the edge data that the predicate consumed was read-only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...tuning.defaults import DEFAULT_TILE_BLOCKS
from ..lowering import resolve_interpret


def _popcount32(x):
    x = x.astype(jnp.uint32)
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    return ((x * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)


def _kernel(bits_ref, keep_ref, subset_ref, bits_out_ref, cnt_ref):
    bits = bits_ref[...]          # (TB, W) uint32
    keep = keep_ref[...]          # (TB, FB) bool
    sub = subset_ref[...]         # (TB,) bool — block owner in the subset
    TB, W = bits.shape
    FB = keep.shape[1]

    # pack the keep predicate into words (vectorized, no per-bit loop)
    k3 = keep.reshape(TB, W, FB // W)
    weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    keep_words = jnp.sum(
        jnp.where(k3, weights[None, None, :], jnp.uint32(0)),
        axis=-1,
        dtype=jnp.uint32,
    )
    new_bits = jnp.where(sub[:, None], bits & keep_words, bits)
    bits_out_ref[...] = new_bits
    cnt_ref[...] = jnp.sum(_popcount32(new_bits), axis=1)


@functools.partial(jax.jit, static_argnames=("tile_blocks", "interpret"))
def filter_pack_pallas(
    bits: jnp.ndarray,     # (NB, W) uint32
    keep: jnp.ndarray,     # (NB, FB) bool
    subset: jnp.ndarray,   # (NB,) bool
    *,
    tile_blocks: int = DEFAULT_TILE_BLOCKS,
    interpret: bool | None = None,
):
    """Returns (new_bits (NB, W) uint32, active_count (NB,) int32)."""
    interpret = resolve_interpret(interpret, kernel="filter_pack")
    NB, W = bits.shape
    FB = keep.shape[1]
    TB = min(tile_blocks, NB)
    pad = (-NB) % TB
    if pad:
        bits = jnp.pad(bits, ((0, pad), (0, 0)))
        keep = jnp.pad(keep, ((0, pad), (0, 0)))
        subset = jnp.pad(subset, (0, pad))
    nb_pad = NB + pad
    grid = (nb_pad // TB,)

    new_bits, cnt = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((TB, W), lambda i: (i, 0)),
            pl.BlockSpec((TB, FB), lambda i: (i, 0)),
            pl.BlockSpec((TB,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((TB, W), lambda i: (i, 0)),
            pl.BlockSpec((TB,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb_pad, W), jnp.uint32),
            jax.ShapeDtypeStruct((nb_pad,), jnp.int32),
        ],
        interpret=interpret,
    )(bits, keep, subset)
    return new_bits[:NB], cnt[:NB]
