"""Graph-backend protocol — one edgeMap engine over three storage formats.

``edge_map`` / ``edgemap_dense`` / ``edgemap_chunked`` / ``edgemap_reduce``
(and everything layered on them: graphFilter, vertexSubset composition, the
algorithm suite) accept any object satisfying ``GraphBackend``:

* ``CSRGraph``       — uncompressed blocked CSR (the seed format)
* ``CompressedCSR``  — Ligra+-style delta-packed blocks (§5.1.3)
* ``DeltaGraph``     — mutable ``base ∪ delta`` overlay (``repro.delta``):
  one of the two formats above as the read-only NVRAM base, plus DRAM
  patch blocks appended after the base block range and tombstone bits
  folded into the block view.  It satisfies the protocol structurally —
  this module never imports it (delta layers ON core) — and takes the
  generic paths below: lazy ``block_dst`` for the dense pass, the
  block-gather tile for the sparse pass (``sparse_streamed`` falls back
  to plain sparse, the documented non-CompressedCSR behavior), so every
  consumer serves a mutated graph unmodified.

The two structural hooks that differ per backend live here:

* ``dense_block_view``  — the full (NB, F_B) target/weight view for the
  dense (pull) pass.  For the compressed backend this is the lazy cumsum
  decode, traced into the consuming gather/segment-reduce; the
  Pallas ``compressed_spmv`` kernel is the explicitly streamed variant.
* ``tile_block_reader`` — reads C-block tiles for the chunked (sparse) pass.
  For the compressed backend this decodes *inside the chunk loop*
  (App. D.1's "decode the whole block to fetch one edge" discipline), so
  peak intermediates stay ``chunk_blocks × F_B`` words for both formats.
"""
from __future__ import annotations

from typing import Callable, Protocol, Union, runtime_checkable

import jax.numpy as jnp

from .compressed import (
    CompressedCSR,
    decode_block_tile,
    decode_blocks,
    exception_dense,
)
from .csr import CSRGraph


@runtime_checkable
class GraphBackend(Protocol):
    """Structural surface every graph execution backend provides."""

    n: int
    m: int
    num_blocks: int
    block_size: int
    block_src: jnp.ndarray  # int32[NB] — owner vertex per block
    degrees: jnp.ndarray    # int32[n]

    @property
    def block_dst(self) -> jnp.ndarray: ...  # int32[NB, FB], sentinel n pads

    @property
    def block_w(self) -> jnp.ndarray: ...    # float32[NB, FB]

    @property
    def edge_valid(self) -> jnp.ndarray: ...  # bool[NB*FB]

    def shard(self, num_shards: int) -> list["GraphBackend"]: ...
    # block-range partition: each shard is a valid backend over the global
    # vertex space (n, degrees replicated; blocks split; non-dividing counts
    # pad with empty blocks).  Consumed by the planner (repro.core.plan).


GraphLike = Union[CSRGraph, CompressedCSR]


def dense_block_view(g: GraphBackend) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(block_dst, block_w), both (NB, F_B) — the dense-pass edge view."""
    return g.block_dst, g.block_w


def tile_block_reader(
    g: GraphBackend,
) -> Callable[[jnp.ndarray], tuple[jnp.ndarray, jnp.ndarray]]:
    """``bids -> (dst, w)``, both (C, F_B), for a tile of block ids.

    ``bids`` rows equal to ``num_blocks`` (the compact_mask fill) yield
    all-sentinel targets / zero weights for both backends.  Anything that
    satisfies ``GraphBackend`` without being a ``CompressedCSR`` takes the
    generic block-gather path.  Build the reader outside a chunk loop: an
    exception-dense ``CompressedCSR`` is decoded here, once, because XLA on
    TPU does not hoist that whole-graph decode out of a ``while_loop`` and
    would redo it for every chunk.
    """
    if isinstance(g, CompressedCSR):
        if exception_dense(g):
            # COO patching would cost O(C·NE) per chunk: decode exactly,
            # once, and gather rows per tile
            decoded = decode_blocks(g)
            decode = lambda bids: jnp.take(  # noqa: E731
                decoded, bids, axis=0, mode="fill", fill_value=g.n
            )
        else:
            decode = lambda bids: decode_block_tile(g, bids)  # noqa: E731

        def read(bids):
            dst = decode(bids)
            if g.block_weights is not None:
                w = jnp.take(g.block_weights, bids, axis=0, mode="fill", fill_value=0.0)
            else:
                w = jnp.ones(dst.shape, jnp.float32)
            return dst, w

        return read

    def read(bids):
        dst = jnp.take(g.block_dst, bids, axis=0, mode="fill", fill_value=g.n)
        w = jnp.take(g.block_w, bids, axis=0, mode="fill", fill_value=0.0)
        return dst, w

    return read
