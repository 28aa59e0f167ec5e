"""Blocked, read-only CSR graph structure — the PSAM "large memory".

The graph is built once on the host (numpy) and never mutated afterwards.
Edges are laid out in fixed-size *blocks* of ``F_B`` slots (the paper's filter
block size, §4.2.1); every block belongs to exactly one source vertex, and a
vertex with degree d owns ``ceil(d / F_B)`` blocks.  Padding slots carry the
sentinel target ``n`` so that gathers/segment-reductions can route them to a
dead row.

Two views of the same storage are kept (both derived, both read-only):

* flat view   — ``edge_src/edge_dst/edge_w`` of length ``NB * F_B``
* block view  — ``block_src[NB]`` plus the flat arrays reshaped ``(NB, F_B)``

On a real TPU the flat/block arrays live in HBM and are streamed block-wise
into VMEM by the Pallas kernels; all mutable per-vertex state is ``O(n)``
words (the PSAM "small memory").
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.trace import ingest_span
from .bands import BandIndex, band_index

DEFAULT_BLOCK_SIZE = 128  # lanes; multiple of 32 so the filter bitset packs into words


def sharded_block_counts(num_blocks: int, num_shards: int) -> tuple[int, int]:
    """(blocks per shard, total blocks incl. padding) for a planner split.

    The single source of truth for the shard partitioning arithmetic:
    ``GraphBackend.shard``, the cost model, the dry-run specs and
    ``shard_blocks_for_mesh`` all derive from it.  Non-dividing counts
    round *up* — the tail shard pads with empty sentinel blocks, it is
    never truncated."""
    per = -(-num_blocks // max(num_shards, 1))
    return per, per * num_shards


@partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "offsets",
        "block_offsets",
        "block_src",
        "edge_src",
        "edge_dst",
        "edge_w",
        "degrees",
        "bands",
    ],
    meta_fields=["n", "m", "num_blocks", "block_size", "weighted"],
)
@dataclasses.dataclass(frozen=True)
class CSRGraph:
    """Immutable blocked-CSR graph (PSAM large memory)."""

    # --- data (device arrays, read-only after build) ---
    offsets: jnp.ndarray        # int32[n+1]   — into flat edge slots (block-padded)
    block_offsets: jnp.ndarray  # int32[n+1]   — into blocks
    block_src: jnp.ndarray      # int32[NB]    — owner vertex of each block
    edge_src: jnp.ndarray       # int32[NB*F_B] (sentinel n on padding)
    edge_dst: jnp.ndarray       # int32[NB*F_B] (sentinel n on padding)
    edge_w: jnp.ndarray         # float32[NB*F_B]
    degrees: jnp.ndarray        # int32[n]     — true degrees
    # --- static metadata ---
    n: int
    m: int                      # true (unpadded) number of directed edge slots
    num_blocks: int
    block_size: int
    weighted: bool
    # the dense sweep's degree bands (``repro.core.bands``); None takes the
    # padded sweep
    bands: BandIndex | None = None

    # ------------------------------------------------------------------
    @property
    def block_dst(self) -> jnp.ndarray:
        return self.edge_dst.reshape(self.num_blocks, self.block_size)

    @property
    def block_w(self) -> jnp.ndarray:
        return self.edge_w.reshape(self.num_blocks, self.block_size)

    @property
    def edge_valid(self) -> jnp.ndarray:
        """bool[NB*F_B] — True on real (non-padding) edge slots."""
        return self.edge_dst < jnp.int32(self.n)

    @property
    def avg_degree(self) -> float:
        return self.m / max(self.n, 1)

    def out_degree(self, v):
        return self.degrees[v]

    def shard(self, num_shards: int) -> list["CSRGraph"]:
        """Partition the block set into ``num_shards`` contiguous ranges.

        Block counts that don't divide ``num_shards`` are padded with *empty*
        blocks (owner = sentinel n, all targets = n, zero weights) so every
        shard carries the same ``ceil(NB / num_shards)`` blocks and the tail
        shard is never truncated.  Each shard keeps the full O(n) vertex
        metadata (``degrees``, ``offsets``) replicated — only the O(m) edge
        blocks split — so a shard is itself a valid ``GraphBackend`` over the
        *global* vertex space: same ``n``, same sentinel, same frontier
        semantics.  The planner (``repro.core.plan``) stacks shards into one
        pytree and runs the ordinary edgeMap bodies per shard inside
        ``shard_map``.  Shards carry no band index (their band sizes would
        differ, and shards stack): their dense sweep is the padded one.
        """
        if num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {num_shards}")
        NB, FB = self.num_blocks, self.block_size
        per, padded_total = sharded_block_counts(NB, num_shards)
        pad = padded_total - NB
        bsrc = np.asarray(self.block_src)
        edst = np.asarray(self.edge_dst).reshape(NB, FB)
        esrc = np.asarray(self.edge_src).reshape(NB, FB)
        ew = np.asarray(self.edge_w).reshape(NB, FB)
        if pad:
            bsrc = np.concatenate([bsrc, np.full(pad, self.n, np.int32)])
            edst = np.concatenate([edst, np.full((pad, FB), self.n, np.int32)])
            esrc = np.concatenate([esrc, np.full((pad, FB), self.n, np.int32)])
            ew = np.concatenate([ew, np.zeros((pad, FB), np.float32)])
        shards = []
        for s in range(num_shards):
            lo, hi = s * per, (s + 1) * per
            shards.append(
                dataclasses.replace(
                    self,
                    block_src=jnp.asarray(bsrc[lo:hi]),
                    edge_src=jnp.asarray(esrc[lo:hi].reshape(-1)),
                    edge_dst=jnp.asarray(edst[lo:hi].reshape(-1)),
                    edge_w=jnp.asarray(ew[lo:hi].reshape(-1)),
                    num_blocks=per,
                    bands=None,
                )
            )
        return shards


def build_csr(
    n: int,
    src: np.ndarray,
    dst: np.ndarray,
    w: np.ndarray | None = None,
    *,
    block_size: int = DEFAULT_BLOCK_SIZE,
    symmetrize: bool = False,
) -> CSRGraph:
    """Build a blocked CSR graph on the host.

    ``src``/``dst`` are directed edge endpoints.  With ``symmetrize=True`` the
    reverse edges are added (and exact duplicates removed), matching the
    paper's symmetrized inputs.
    """
    with ingest_span("dedupe"):
        src = np.asarray(src, dtype=np.int64)
        dst = np.asarray(dst, dtype=np.int64)
        if w is None:
            weighted = False
            w = np.ones_like(src, dtype=np.float32)
        else:
            weighted = True
            w = np.asarray(w, dtype=np.float32)

        if symmetrize:
            src, dst, w = (
                np.concatenate([src, dst]),
                np.concatenate([dst, src]),
                np.concatenate([w, w]),
            )
        # drop self loops, dedupe; np.unique returns the keys sorted, so the
        # edges come out ordered by (src, dst)
        keep = src != dst
        src, dst, w = src[keep], dst[keep], w[keep]
        key = src * n + dst
        _, uniq = np.unique(key, return_index=True)
        src, dst, w = src[uniq], dst[uniq], w[uniq]
        m = int(src.shape[0])

    with ingest_span("layout"):
        deg = np.bincount(src, minlength=n).astype(np.int64)
        nblk = np.maximum((deg + block_size - 1) // block_size, 0)
        block_offsets = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(nblk, out=block_offsets[1:])
        num_blocks = int(block_offsets[-1])
        num_blocks = max(num_blocks, 1)  # keep shapes non-degenerate
        if int(block_offsets[-1]) == 0:
            block_offsets[-1] = 1  # single dummy block owned by sentinel

        slots = num_blocks * block_size
        edge_src = np.full(slots, n, dtype=np.int32)
        edge_dst = np.full(slots, n, dtype=np.int32)
        edge_w = np.zeros(slots, dtype=np.float32)

        offsets = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(nblk * block_size, out=offsets[1:])
        # scatter edges into their padded slots
        starts = offsets[src]
        within = np.zeros(m, dtype=np.int64)
        if m:
            # position of each edge within its vertex's run (src-sorted)
            first_of_run = np.concatenate([[True], src[1:] != src[:-1]])
            run_ids = np.cumsum(first_of_run) - 1
            run_starts = np.flatnonzero(first_of_run)
            within = np.arange(m) - run_starts[run_ids]
        pos = starts + within
        edge_src[pos] = src.astype(np.int32)
        edge_dst[pos] = dst.astype(np.int32)
        edge_w[pos] = w

        block_src = np.full(num_blocks, n, dtype=np.int32)
        for_v = np.repeat(np.arange(n, dtype=np.int32), nblk)
        block_src[: for_v.shape[0]] = for_v
        # a block's valid count from its owner's degree: every block of a
        # vertex is full but its last
        valid = np.zeros(num_blocks, dtype=np.int64)
        rank = np.arange(for_v.shape[0]) - block_offsets[for_v]
        valid[: for_v.shape[0]] = np.minimum(deg[for_v] - rank * block_size, block_size)

        g = CSRGraph(
            offsets=jnp.asarray(offsets, dtype=jnp.int32),
            block_offsets=jnp.asarray(block_offsets, dtype=jnp.int32),
            block_src=jnp.asarray(block_src),
            edge_src=jnp.asarray(edge_src),
            edge_dst=jnp.asarray(edge_dst),
            edge_w=jnp.asarray(edge_w),
            degrees=jnp.asarray(deg, dtype=jnp.int32),
            n=int(n),
            m=m,
            num_blocks=num_blocks,
            block_size=int(block_size),
            weighted=weighted,
            bands=band_index(valid, block_src, block_size),
        )
    return g


def graph_spec(n: int, num_blocks: int, block_size: int, weighted: bool = False):
    """ShapeDtypeStruct stand-in for a CSRGraph (used by the dry-run)."""
    s = jax.ShapeDtypeStruct
    slots = num_blocks * block_size
    return CSRGraph(
        offsets=s((n + 1,), jnp.int32),
        block_offsets=s((n + 1,), jnp.int32),
        block_src=s((num_blocks,), jnp.int32),
        edge_src=s((slots,), jnp.int32),
        edge_dst=s((slots,), jnp.int32),
        edge_w=s((slots,), jnp.float32),
        degrees=s((n,), jnp.int32),
        n=n,
        m=slots,
        num_blocks=num_blocks,
        block_size=block_size,
        weighted=weighted,
    )
