"""Degree bands — the dense edgeMap sweep's routing index over the blocks.

A vertex of degree d owns ``ceil(d / F_B)`` blocks and only its last block
is partial, so on a skewed graph most blocks hold a few real slots and the
rest is sentinel padding.  The dense sweep (``repro.core.edgemap``)
scatters one update per slot it reads, at the same price whether the slot
is real or padding, so it reads each block only up to its *band width*:
the power-of-two ceiling of the block's valid count, at least
``MIN_BAND_WIDTH`` lanes and at most ``F_B``.  Blocks of one width form a
band; the sweep row-gathers a band's blocks, trims them to the width and
scatters what is left.  The storage layout does not change.

The index is O(NB) words, read-only, and built on the host at ingest
(``build_csr`` from the degrees, carried over and checked by ``compress``):
a band's size is a static shape, so it must be known before ``jit``.
Whatever changes a graph's block set must build a fresh index or drop it
(``bands=None``): a graph without one takes the padded sweep.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

MIN_BAND_WIDTH = 8


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["block_ids", "owners"],
    meta_fields=["widths", "sizes"],
)
@dataclasses.dataclass(frozen=True)
class BandIndex:
    """Blocks grouped by band width, narrowest band first."""

    block_ids: jnp.ndarray   # int32[NB] — block ids in band order
    owners: jnp.ndarray      # int32[NB] — block_src[block_ids]
    widths: tuple[int, ...]  # lanes the sweep reads of each band's blocks
    sizes: tuple[int, ...]   # blocks per band (no band is empty)

    def spans(self) -> list[tuple[int, int, int]]:
        """``(width, start, rows)`` of each band in ``block_ids`` order."""
        starts = np.cumsum((0,) + self.sizes[:-1])
        return [(w, int(s), r) for w, s, r in zip(self.widths, starts, self.sizes)]


def band_widths(valid_count, block_size: int) -> np.ndarray:
    """Each block's band width: the power-of-two ceiling of its valid count,
    clipped to ``[MIN_BAND_WIDTH, block_size]``."""
    vc = np.maximum(np.asarray(valid_count, np.int64), 1)
    pow2 = np.left_shift(1, np.ceil(np.log2(vc)).astype(np.int64))
    return np.clip(pow2, min(MIN_BAND_WIDTH, block_size), block_size)


def band_index(valid_count, block_src, block_size: int) -> BandIndex:
    """The band index of a block set (host-side, concrete arrays).

    ``valid_count[b]`` is block b's real slots (front-packed), ``block_src``
    its owner.  Within a band the blocks keep their storage order."""
    width = band_widths(valid_count, block_size)
    order = np.argsort(width, kind="stable").astype(np.int32)
    widths, sizes = np.unique(width, return_counts=True)
    return BandIndex(
        block_ids=jnp.asarray(order),
        owners=jnp.asarray(np.asarray(block_src, np.int32)[order]),
        widths=tuple(int(w) for w in widths),
        sizes=tuple(int(s) for s in sizes),
    )


def check_band_index(bands: BandIndex, valid_count, block_src, block_size: int) -> None:
    """Raise ``ValueError`` unless ``bands`` indexes exactly this block set:
    a permutation of the blocks, each in its valid count's band, with its
    owner alongside."""
    ids = np.asarray(bands.block_ids)
    vc = np.asarray(valid_count)
    if ids.shape != vc.shape or np.any(np.bincount(ids, minlength=vc.size) != 1):
        raise ValueError("band index is not a permutation of the graph's blocks")
    if not np.array_equal(
        np.repeat(bands.widths, bands.sizes), band_widths(vc[ids], block_size)
    ):
        raise ValueError("band index does not match the blocks' valid counts")
    if not np.array_equal(np.asarray(bands.owners), np.asarray(block_src)[ids]):
        raise ValueError("band index owners do not match block_src")
