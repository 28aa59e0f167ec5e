"""Compressed blocked CSR — the Ligra+ byte-code format (§5.1.3) adapted to
TPU fixed-width decoding.

The paper's web-graph inputs are stored with per-block difference encoding
and decoded block-at-a-time; the graphFilter block size is tied to the
compression block size (§4.2.1).  Byte-aligned varints are a sequential
CPU format, so the TPU-idiomatic equivalent is **fixed-width delta
packing**: per block we store the first target (int32) and uint16 deltas
between consecutive sorted targets; the rare deltas ≥ 2¹⁶ go to a COO
exception list.  Decoding a block is a vectorized cumsum over the lane
dimension — exactly the "decode the whole block to fetch one edge"
discipline the paper's filter iterator uses (App. D.1) — and the
graphFilter bits apply unchanged on top of the decoded block.

``CompressedCSR`` is a first-class execution backend: it exposes the same
block view (``block_src`` / ``block_dst`` / ``block_w`` / ``edge_valid``)
that ``edge_map`` and the graphFilter consume, with the decoded arrays
produced lazily inside the consuming program (the Pallas kernel in
``repro.kernels.compressed_spmv`` streams the raw uint16 deltas directly).
XLA decides what it fuses: on a v5e the dense sweep still writes the
decoded int32 targets to HBM once per round, as the scatter's index
operand (PERF.md, the decode layer).

Compression ratio: 32-bit targets → ~16.25 bits/edge + exceptions, i.e.
~2× on locality-friendly orderings (the paper reports 2.7–2.9× with
byte codes on web graphs).  Weights (when present) do not delta-compress
and are carried uncompressed.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.trace import ingest_span, scoped
from .bands import BandIndex, check_band_index
from .csr import CSRGraph, sharded_block_counts

ESCAPE = np.uint16(0xFFFF)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "block_first",
        "deltas",
        "valid_count",
        "exc_block",
        "exc_slot",
        "exc_value",
        "block_src",
        "degrees",
        "block_weights",
        "bands",
    ],
    meta_fields=[
        "n",
        "m",
        "num_blocks",
        "block_size",
        "n_exceptions",
        "weighted",
        "exception_dense_hint",
    ],
)
@dataclasses.dataclass(frozen=True)
class CompressedCSR:
    """Read-only difference-encoded blocked CSR (PSAM large memory)."""

    block_first: jnp.ndarray  # int32[NB]       — first target per block
    deltas: jnp.ndarray       # uint16[NB, FB]  — deltas[:, 0] unused (=0)
    valid_count: jnp.ndarray  # uint16[NB]      — real (non-padding) slots, front-packed
    exc_block: jnp.ndarray    # int32[NE]       — exception coordinates
    exc_slot: jnp.ndarray     # int32[NE]
    exc_value: jnp.ndarray    # int32[NE]       — true delta value
    block_src: jnp.ndarray    # int32[NB]
    degrees: jnp.ndarray      # int32[n]
    n: int
    m: int
    num_blocks: int
    block_size: int
    n_exceptions: int
    block_weights: jnp.ndarray | None = None  # float32[NB, FB] when weighted
    weighted: bool = False
    # set by shard(): the whole-graph exception-density verdict, so every
    # shard keeps the original decode-strategy choice (a shard's padded
    # exception list and shrunken block count would skew the ratio test)
    exception_dense_hint: bool | None = None
    # the dense sweep's degree bands (``repro.core.bands``); None takes the
    # padded sweep
    bands: BandIndex | None = None

    @property
    def compressed_bytes(self) -> int:
        return int(
            self.block_first.size * 4
            + self.deltas.size * 2
            + self.valid_count.size * 2
            + self.n_exceptions * 12
        )

    @property
    def uncompressed_bytes(self) -> int:
        return int(self.deltas.size * 4)

    @property
    def compression_ratio(self) -> float:
        return self.uncompressed_bytes / max(self.compressed_bytes, 1)

    @property
    def avg_degree(self) -> float:
        return self.m / max(self.n, 1)

    def out_degree(self, v):
        return self.degrees[v]

    # ------------------------------------------------------------------
    # Backend view — same surface the uncompressed CSRGraph offers.  The
    # decoded arrays are *lazy*: under jit the cumsum decode is traced into
    # whatever consumes it (edgeMap's gather/segment-reduce), under the
    # scope ``sage.decode``.
    # ------------------------------------------------------------------
    @property
    def block_dst(self) -> jnp.ndarray:
        """Decoded int32[NB, FB] targets (sentinel n on padding slots)."""
        return decode_blocks(self)

    @property
    def block_w(self) -> jnp.ndarray:
        if self.block_weights is not None:
            return self.block_weights
        return jnp.ones((self.num_blocks, self.block_size), jnp.float32)

    @property
    def edge_dst(self) -> jnp.ndarray:
        return decode_blocks(self).reshape(-1)

    @property
    def edge_src(self) -> jnp.ndarray:
        """int32[NB*F_B] — owner per slot, sentinel n on padding (the exact
        CSRGraph padding contract, so src == n neutralizes padding for any
        consumer keyed on out-of-range sources)."""
        src = jnp.broadcast_to(
            self.block_src[:, None], (self.num_blocks, self.block_size)
        ).reshape(-1)
        return jnp.where(self.edge_valid, src, jnp.int32(self.n))

    @property
    def edge_w(self) -> jnp.ndarray:
        return self.block_w.reshape(-1)

    @property
    def edge_valid(self) -> jnp.ndarray:
        """bool[NB*F_B] — True on real (non-padding) edge slots.

        Structural: read straight off ``valid_count``, no decode needed —
        makeFilter on a compressed graph never touches the delta stream.
        """
        lane = jnp.arange(self.block_size, dtype=jnp.int32)
        vc = self.valid_count.astype(jnp.int32)
        return (lane[None, :] < vc[:, None]).reshape(-1)

    def shard(self, num_shards: int) -> list["CompressedCSR"]:
        """Partition the compressed block set into ``num_shards`` ranges.

        Compressed blocks are independently decodable (per-block first target
        + deltas + valid count), so sharding is a block-range split of the
        delta stream plus a *per-shard exception list*: each COO exception is
        routed to the shard owning its block, with the block coordinate
        rebased to the shard-local range.  Exception lists are padded to the
        max count across shards (padding rows use the out-of-range block id
        ``per``, which every decoder drops) so shards stack into one pytree
        with identical meta.  Block counts that don't divide pad with empty
        blocks (valid_count 0, owner = sentinel n) — the tail shard is never
        truncated.  Vertex metadata (``degrees``) stays replicated per shard.
        Shards carry no band index: their dense sweep is the padded one.
        """
        if num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {num_shards}")
        NB, FB = self.num_blocks, self.block_size
        per, padded_total = sharded_block_counts(NB, num_shards)
        pad = padded_total - NB
        first = np.asarray(self.block_first)
        deltas = np.asarray(self.deltas)
        vc = np.asarray(self.valid_count)
        bsrc = np.asarray(self.block_src)
        bw = None if self.block_weights is None else np.asarray(self.block_weights)
        if pad:
            first = np.concatenate([first, np.zeros(pad, np.int32)])
            deltas = np.concatenate([deltas, np.zeros((pad, FB), np.uint16)])
            vc = np.concatenate([vc, np.zeros(pad, np.uint16)])
            bsrc = np.concatenate([bsrc, np.full(pad, self.n, np.int32)])
            if bw is not None:
                bw = np.concatenate([bw, np.zeros((pad, FB), np.float32)])
        eb = np.asarray(self.exc_block)
        es = np.asarray(self.exc_slot)
        ev = np.asarray(self.exc_value)
        sel = [(eb >= s * per) & (eb < (s + 1) * per) for s in range(num_shards)]
        ne_max = max((int(m.sum()) for m in sel), default=0)
        shards = []
        for s in range(num_shards):
            lo, hi = s * per, (s + 1) * per
            m = sel[s]
            k = int(m.sum())
            # pad rows target block id ``per`` (out of the shard's range):
            # decode_blocks scatter-drops them, decode_block_tile patches a
            # delta of 0 into lane 0 of the fill row, which is zeroed anyway
            leb = np.full(ne_max, per, np.int32)
            les = np.zeros(ne_max, np.int32)
            lev = np.zeros(ne_max, np.int32)
            leb[:k] = eb[m] - lo
            les[:k] = es[m]
            lev[:k] = ev[m]
            shards.append(
                dataclasses.replace(
                    self,
                    block_first=jnp.asarray(first[lo:hi]),
                    deltas=jnp.asarray(deltas[lo:hi]),
                    valid_count=jnp.asarray(vc[lo:hi]),
                    exc_block=jnp.asarray(leb),
                    exc_slot=jnp.asarray(les),
                    exc_value=jnp.asarray(lev),
                    block_src=jnp.asarray(bsrc[lo:hi]),
                    num_blocks=per,
                    n_exceptions=ne_max,
                    block_weights=None if bw is None else jnp.asarray(bw[lo:hi]),
                    exception_dense_hint=exception_dense(self),
                    bands=None,
                )
            )
        return shards


def compress(g: CSRGraph) -> CompressedCSR:
    """Host-side encoder (runs once at load, like the paper's preprocessing).

    Padding slots (sentinel n in the CSR) are encoded as *repeats of the
    last real target* — delta 0 — and validity is carried structurally as a
    per-block count (slots are front-packed by build_csr).  The decoders
    re-insert the sentinel on padding slots, so
    ``decode_blocks(compress(g)) == g.block_dst`` bit for bit while the
    exception list stays tied to true ≥2¹⁶ adjacency gaps — without this,
    every padded block on a graph with n > 2¹⁶ would land on the exception
    list and the "rare path" would stop being rare.  Weighted graphs keep
    their weights uncompressed alongside the delta-packed targets.  The
    CSR's band index (``repro.core.bands``) is carried over, checked
    against the encoded valid counts.
    """
    NB, FB = g.num_blocks, g.block_size
    with ingest_span("fetch"):
        dst = np.asarray(g.edge_dst)
    with ingest_span("encode"):
        dst = dst.reshape(NB, FB).astype(np.int64)
        vc = (dst < g.n).sum(axis=1).astype(np.int64)  # front-packed real slots
        last = np.where(vc > 0, dst[np.arange(NB), np.maximum(vc - 1, 0)], 0)
        lane = np.arange(FB)[None, :]
        dst_enc = np.where(lane < vc[:, None], dst, last[:, None])
        first = dst_enc[:, 0].astype(np.int32)
        prev = dst_enc[:, :-1]
        cur = dst_enc[:, 1:]
        raw = cur - prev
        raw = np.concatenate([np.zeros((NB, 1), np.int64), raw], axis=1)
        over = (raw >= int(ESCAPE)) | (raw < 0)
        deltas = np.where(over, int(ESCAPE), raw).astype(np.uint16)
        eb, es = np.nonzero(over)
        if g.bands is not None:
            check_band_index(g.bands, vc, g.block_src, FB)
        c = CompressedCSR(
            block_first=jnp.asarray(first),
            deltas=jnp.asarray(deltas),
            valid_count=jnp.asarray(vc.astype(np.uint16)),
            exc_block=jnp.asarray(eb.astype(np.int32)),
            exc_slot=jnp.asarray(es.astype(np.int32)),
            exc_value=jnp.asarray(raw[eb, es].astype(np.int32)),
            block_src=g.block_src,
            degrees=g.degrees,
            n=g.n,
            m=g.m,
            num_blocks=NB,
            block_size=FB,
            n_exceptions=int(eb.shape[0]),
            block_weights=g.block_w if g.weighted else None,
            weighted=g.weighted,
            bands=g.bands,
        )
    return c


def _lane_iota(c: CompressedCSR) -> jnp.ndarray:
    return jnp.arange(c.block_size, dtype=jnp.int32)


@scoped("sage.decode")
def decode_blocks(c: CompressedCSR) -> jnp.ndarray:
    """Decode ALL blocks → int32[NB, FB] targets (vectorized cumsum).

    Padding slots come back as the sentinel n (structural ``valid_count``
    mask), bit-identical to the uncompressed ``block_dst``.  O(m) work /
    O(log F_B) depth per block, matching the paper's block decode cost;
    used by edgeMap over compressed graphs.
    """
    d = c.deltas.astype(jnp.int32)
    # patch exceptions (escaped wide deltas)
    if c.n_exceptions:
        d = d.at[c.exc_block, c.exc_slot].set(c.exc_value, mode="drop")
    d = d.at[:, 0].set(0)
    raw = c.block_first[:, None] + jnp.cumsum(d, axis=1, dtype=jnp.int32)
    valid = _lane_iota(c)[None, :] < c.valid_count.astype(jnp.int32)[:, None]
    return jnp.where(valid, raw, jnp.int32(c.n))


def decode_block(c: CompressedCSR, bid) -> jnp.ndarray:
    """Decode a single block (the filter-iterator path, App. D.1)."""
    d = jnp.take(c.deltas, bid, axis=0).astype(jnp.int32)
    if c.n_exceptions:
        hit = c.exc_block == bid
        d = d.at[jnp.where(hit, c.exc_slot, c.block_size)].set(
            jnp.where(hit, c.exc_value, 0), mode="drop"
        )
    d = d.at[0].set(0)
    raw = jnp.take(c.block_first, bid) + jnp.cumsum(d, dtype=jnp.int32)
    vc = jnp.take(c.valid_count, bid).astype(jnp.int32)
    return jnp.where(_lane_iota(c) < vc, raw, jnp.int32(c.n))


def exception_dense(c: CompressedCSR) -> bool:
    """Static (metadata-only) test: is the exception list too dense for the
    per-tile COO patch to stay a rare path?  Past this point consumers
    should decode exactly instead (the compression is doing little on such
    id-locality-free graphs anyway).  Shards carry the whole-graph verdict
    as a hint — their padded exception lists and shrunken block counts
    would otherwise inflate the ratio."""
    if c.exception_dense_hint is not None:
        return c.exception_dense_hint
    return c.n_exceptions > max(16, min(c.num_blocks // 4, 4096))


@scoped("sage.decode")
def decode_block_tile(c: CompressedCSR, bids: jnp.ndarray) -> jnp.ndarray:
    """Decode a tile of blocks → int32[C, FB] (the chunk-loop path, §4.1).

    ``bids`` may contain the fill value ``num_blocks`` (or anything out of
    range): those rows decode to all-sentinel (target == n), matching the
    uncompressed chunk gather with ``fill_value=n``.  Peak intermediate is
    ``C × F_B`` words — never proportional to the whole edge set.

    Precondition: real block ids in ``bids`` must be unique (chunk tiles are
    compacted indices, so this always holds there) — a duplicated id would
    get its exceptions patched only into its first row.  For decoding the
    exception list itself (which can repeat a block), vmap ``decode_block``.
    The patch is O(C · NE) boolean compares + an O(NE) scatter per tile.
    """
    C = bids.shape[0]
    d = jnp.take(c.deltas, bids, axis=0, mode="fill", fill_value=0).astype(jnp.int32)
    if c.n_exceptions:
        # route each exception to the (unique) tile row holding its block;
        # exceptions whose block is not in the tile scatter-drop at row C
        match = bids[:, None] == c.exc_block[None, :]                      # (C, NE)
        hit = jnp.any(match, axis=0)
        row = jnp.where(hit, jnp.argmax(match, axis=0), jnp.int32(C))
        d = d.at[row, c.exc_slot].set(c.exc_value, mode="drop")
    d = d.at[:, 0].set(0)
    first = jnp.take(c.block_first, bids, mode="fill", fill_value=c.n)
    raw = first[:, None] + jnp.cumsum(d, axis=1, dtype=jnp.int32)
    vc = jnp.take(c.valid_count, bids, mode="fill", fill_value=0).astype(jnp.int32)
    return jnp.where(_lane_iota(c)[None, :] < vc[:, None], raw, jnp.int32(c.n))


def edgemap_sum_compressed(
    c: CompressedCSR, x: jnp.ndarray, *, edge_active: jnp.ndarray | None = None
) -> jnp.ndarray:
    """out[v] = Σ over decoded active edges (v,u) of x[u] — PageRank-style
    aggregation straight off the compressed representation (with optional
    graphFilter bits), proving filter ∘ compression composes as in §4.2.1."""
    n = c.n
    dst = decode_blocks(c)
    valid = dst < n
    if edge_active is not None:
        valid = valid & edge_active.reshape(dst.shape)
    safe = jnp.where(valid, dst, 0)
    xv = jnp.take(x, safe.reshape(-1), axis=0).reshape(dst.shape)
    contrib = jnp.where(valid, xv, 0.0)
    per_block = jnp.sum(contrib, axis=1)
    return jax.ops.segment_sum(per_block, c.block_src, num_segments=n + 1)[:n]
