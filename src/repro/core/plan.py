"""Unified execution planner — one edgeMap, any device count, any backend.

Sage's central claim (§3) is that a single semi-asymmetric engine serves
every graph kernel: edges are read-only "large memory", all mutation stays
in O(n) words of "small memory".  This module is that claim at the
execution layer.  An :class:`ExecutionPlan` names *where* and *how* an
edgeMap runs — device mesh (or none), storage backend (raw or compressed
CSR), dense/sparse/auto strategy, cross-shard reduce shape — and
``edgemap_reduce`` / ``edge_map`` accept one via their ``plan=`` keyword,
so algorithm code never picks an engine:

        vertex state (O(n), replicated) ──┐
                                          ▼
    CSRGraph ────────┐          ┌── edgemap_dense ──┐
                     ├─ shard ──┤                   ├─ psum/pmin/pmax ─► out
    CompressedCSR ───┘  (plan)  └── edgemap_chunked ┘   (per round,
                                                         O(n) words)

Sharded execution reuses the *same* ``edgemap_dense`` / ``edgemap_chunked``
bodies as the single-device path: each shard is a valid ``GraphBackend``
over the global vertex space (``GraphBackend.shard`` splits the block set;
compressed blocks are independently decodable, so sharding the delta stream
is a block-range split plus per-shard exception lists), and ``shard_map``
runs the local body with the frontier and vertex state replicated.  The
only cross-shard traffic is the monoid combine of the O(n) output — never
O(m) — which is the PSAM small-memory bound expressed as a communication
bound (§5.2).

Batched serving rides the same dispatch: ``sharded_edgemap_reduce_batched``
runs B queries through each shard's one local edge sweep and combines the
O(B·n) output — the ``QueryEngine`` (``repro.serving``) drains its batch
buckets through this path unchanged, single-device or sharded.

GraphFilter bits and per-call traversal masks (``edge_active``) are
planner-native: the packed uint32 filter words are block-aligned, so they
partition exactly like the edge blocks (``shard_edge_active`` — the same
ceil(NB/k) block-range split, zero-padded tail) and travel the mesh at one
bit per edge slot.  Each shard unpacks its own words locally inside the
``shard_map`` body, so filtered edgeMaps run sharded with no fallback and
no O(m)-word mask traffic.  ``filter ∘ shard == shard ∘ filter`` by
construction (tested property).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

import numpy as np

from ..obs import exp_buckets, get_registry
from ..tuning.defaults import DEFAULT_CHUNK_BLOCKS, DEFAULT_DENSE_FRAC
from .compressed import CompressedCSR, exception_dense
from .csr import CSRGraph, graph_spec, sharded_block_counts
from .graph_filter import edge_active_words


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["shards"],
    meta_fields=["num_shards", "orig_num_blocks"],
)
@dataclasses.dataclass(frozen=True)
class ShardedGraph:
    """A graph backend split into per-shard block sets, stacked leaf-wise.

    ``shards`` is a single ``CSRGraph`` / ``CompressedCSR`` pytree whose
    array leaves carry a leading ``num_shards`` dimension (shard s of leaf
    ``a`` is ``a[s]``); its static meta describes one shard (``num_blocks``
    is the per-shard block count; ``n``/``m`` stay global).
    ``orig_num_blocks`` records the pre-split global block count so filter
    words can be validated exactly against the graph they were built for.
    Produced by :meth:`ExecutionPlan.prepare`; consumed by the sharded
    edgeMap executor, which partitions the leading dimension across the
    mesh.
    """

    shards: Any
    num_shards: int
    orig_num_blocks: int | None = None

    @property
    def n(self) -> int:
        return self.shards.n

    @property
    def m(self) -> int:
        return self.shards.m

    @property
    def block_size(self) -> int:
        return self.shards.block_size

    @property
    def blocks_per_shard(self) -> int:
        return self.shards.num_blocks

    @property
    def degrees(self) -> jnp.ndarray:
        """int32[n] — O(n) vertex state, replicated per shard (shard 0's copy)."""
        return self.shards.degrees[0]


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["words", "live_ids"],
    meta_fields=["num_shards"],
)
@dataclasses.dataclass(frozen=True)
class ShardedEdgeActive:
    """Shard-local filter state: packed uint32 words, stacked leaf-wise.

    ``words`` is uint32[num_shards, blocks_per_shard, F_B/32] — shard s's
    rows line up 1:1 with shard s of the matching ``ShardedGraph`` (same
    block-range split, zero-padded tail).  Produced by
    :func:`shard_edge_active` / :meth:`ExecutionPlan.prepare`; consumed by
    the sharded edgeMap executor, which partitions the leading dimension
    across the mesh and unpacks locally in each ``shard_map`` body.

    ``live_ids`` (optional) records a live-block compaction
    (``ExecutionPlan.prepare(..., compact_live=True)`` /
    :func:`compact_live_blocks`): int32[num_shards, blocks_per_shard] of
    *original* block ids, padded with the pre-compaction block count — row
    j of shard s's words masks original block ``live_ids[s, j]``.  The
    executor never needs it (a compacted graph is just a smaller block
    set); it exists so cost models and tests can audit exactly which NVRAM
    blocks each shard streams.
    """

    words: jnp.ndarray
    num_shards: int
    live_ids: jnp.ndarray | None = None

    @property
    def blocks_per_shard(self) -> int:
        return self.words.shape[1]


def compact_live_blocks(g, edge_active):
    """Drop dead blocks from a backend under a long-lived filter (host-side).

    The paper's empty-block compaction (§4.2.2), applied *physically* and —
    crucially for the sharded path — **before the shard split**: a block
    none of whose edge slots is active under ``edge_active`` can never
    contribute to any edgeMap that carries this filter, so it should not
    occupy a slot in any shard's block range, let alone stream.  Returns
    ``(g_live, words_live, live_ids)``:

    * ``g_live``     — the same backend type over only the live blocks
      (vertex space untouched: ``n``/``m``/``degrees`` stay global, exactly
      like ``GraphBackend.shard``'s contract).  ``CompressedCSR`` keeps its
      per-block independence — the delta rows gather by live id and the COO
      exception list is filtered to live blocks and re-keyed to compacted
      positions; the whole-graph ``exception_dense`` verdict is pinned as
      the hint, as in ``shard``.
    * ``words_live`` — the packed filter words for the surviving rows
      (uint32[k, F_B/32], aligned 1:1 with ``g_live``'s blocks).
    * ``live_ids``   — int32[k] original block ids, the audit trail that
      ``ShardedEdgeActive.live_ids`` carries through the shard split.

    A filter with no live blocks degenerates to one all-dead block (shapes
    stay non-degenerate, nothing real streams).  Host-side only (concrete
    arrays), like every other prepare-time step.
    """
    words = np.asarray(edge_active_words(edge_active, g.block_size))
    if words.shape[0] != g.num_blocks:
        raise ValueError(
            f"edge_active covers {words.shape[0]} blocks, graph has "
            f"{g.num_blocks} — was the filter built for a different graph?"
        )
    live = np.nonzero(words.any(axis=1))[0].astype(np.int32)
    if live.size == 0:
        # keep shapes non-degenerate: one block, fully masked off
        live = np.zeros(1, np.int32)
        words = np.zeros_like(words)
    live_ids = jnp.asarray(live)
    words_live = jnp.asarray(words[live])
    if isinstance(g, CompressedCSR):
        eb = np.asarray(g.exc_block)
        keep = np.isin(eb, live)
        keep_idx = jnp.asarray(np.nonzero(keep)[0])
        pos = np.full(g.num_blocks + 1, -1, np.int32)
        pos[live] = np.arange(live.size, dtype=np.int32)
        g_live = dataclasses.replace(
            g,
            block_first=g.block_first[live_ids],
            deltas=g.deltas[live_ids],
            valid_count=g.valid_count[live_ids],
            exc_block=jnp.asarray(pos[eb[keep]]),
            exc_slot=g.exc_slot[keep_idx],
            exc_value=g.exc_value[keep_idx],
            block_src=g.block_src[live_ids],
            num_blocks=int(live.size),
            n_exceptions=int(keep.sum()),
            block_weights=(
                None if g.block_weights is None else g.block_weights[live_ids]
            ),
            exception_dense_hint=exception_dense(g),
        )
    elif isinstance(g, CSRGraph):
        NB, FB = g.num_blocks, g.block_size
        g_live = dataclasses.replace(
            g,
            block_src=g.block_src[live_ids],
            edge_src=g.edge_src.reshape(NB, FB)[live_ids].reshape(-1),
            edge_dst=g.edge_dst.reshape(NB, FB)[live_ids].reshape(-1),
            edge_w=g.edge_w.reshape(NB, FB)[live_ids].reshape(-1),
            num_blocks=int(live.size),
        )
    else:
        raise TypeError(f"cannot compact {type(g).__name__}")
    return g_live, words_live, live_ids


def shard_edge_active(
    edge_active,
    *,
    block_size: int,
    blocks_per_shard: int,
    num_shards: int,
    num_blocks: int | None = None,
) -> ShardedEdgeActive:
    """Partition filter words alongside the edge blocks (block-range split).

    ``edge_active`` is any form ``edge_active_words`` accepts (GraphFilter,
    packed uint32 words, bool slot mask) over the *global* block set; the
    result stacks per-shard word tiles whose rows align with
    ``GraphBackend.shard``'s block ranges.  The zero-padded tail rows mask
    the empty sentinel blocks that pad a non-dividing block count (an
    all-zero word deactivates nothing real).  Pure pad+reshape — traceable,
    so per-round filter snapshots shard inside jit'd algorithm loops.

    ``num_blocks``: the graph's true (pre-split) block count, when the
    caller knows it (``ShardedGraph.orig_num_blocks``) — validated exactly.
    Without it, a pad of a whole shard's worth or more is still rejected
    (a filter for this graph pads < num_shards rows).  Zero-filling a
    too-short filter would silently deactivate real blocks, so both checks
    fail as loudly as the single-device reshape does.
    """
    words = edge_active_words(edge_active, block_size)
    total = blocks_per_shard * num_shards
    pad = total - words.shape[0]
    if (
        num_blocks is not None and words.shape[0] != num_blocks
    ) or pad < 0 or pad >= num_shards:
        raise ValueError(
            f"edge_active covers {words.shape[0]} blocks but the plan "
            f"carries {total} ({num_shards} shards x {blocks_per_shard}"
            + (f", graph has {num_blocks}" if num_blocks is not None else "")
            + ") — was the filter built for a different graph?"
        )
    if pad:
        words = jnp.pad(words, ((0, pad), (0, 0)))
    return ShardedEdgeActive(
        words=words.reshape(num_shards, blocks_per_shard, words.shape[-1]),
        num_shards=num_shards,
    )


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Static description of how an edgeMap executes.

    mesh        — jax Mesh, or None for plain single-device execution
    shard_axes  — mesh axes the edge blocks shard over (() → all axes);
                  vertex state is replicated over every axis either way
    backend     — 'csr' | 'compressed' | 'delta' | 'auto' (informational;
                  recorded by make_plan from the graph so cost models /
                  benchmarks can report what actually ran — 'delta' is the
                  repro.delta overlay backend, whose base alone counts as
                  NVRAM)
    strategy    — default edgeMap mode when the call site doesn't pass one:
                  'dense' (pull over all blocks), 'sparse' (chunked over
                  frontier-owned blocks), 'sparse_streamed' (chunked with
                  the frontier-sparse Pallas decode: only live compressed
                  tiles stream HBM→VMEM; non-compressed backends fall back
                  to 'sparse'), 'auto' (Beamer direction opt.)
    reduce_mode — cross-shard combine for the sum monoid: 'flat' psums the
                  O(n) vector over every shard axis; 'hierarchical'
                  reduce-scatters along the fastest axis first (wire bytes
                  on slow axes drop by the fast-axis width, §5.2)
    state_dtype — reduce in a narrower dtype (e.g. bf16), the graph-engine
                  analogue of gradient compression
    chunk_blocks— chunk size for the sparse strategy
    dense_frac  — Beamer threshold: dense when frontier degree > m/dense_frac
                  (measured plans carry 1/d* for the calibrated dense/sparse
                  crossover density d* instead of the hand-picked constant)
    auto_sparse — which sparse flavor the 'auto' strategy's sparse branch
                  runs: 'sparse' | 'sparse_streamed' (calibration picks the
                  one that measured cheaper; non-streaming backends fall
                  back inside edgemap_chunked either way)
    dense_frac_batched — Beamer threshold for BATCHED rounds, from the
                  batched density sweep's own crossover: the batched dense
                  body amortizes one shared sweep over all B lanes, so
                  dense wins batched at far lower densities than
                  single-query and the single-query crossover does not
                  transfer
    auto_sparse_batched — the sparse flavor for BATCHED auto rounds,
                  calibrated separately because the crossover is
                  B-dependent: the streamed union path runs one live-block
                  loop shared by all B lanes while plain sparse vmaps B
                  chunk loops, so streaming can win batched while losing
                  single-query
    batched_flavor_crossover — measured density below which the batched
                  streamed union actually wins: when set (and
                  auto_sparse_batched is 'sparse_streamed'), batched auto's
                  sparse branch picks its flavor at runtime from the
                  batch's mean lane density; None runs the static flavor
                  unconditionally
    interpret   — the resolved Pallas lowering every kernel under this plan
                  runs with: False = native Mosaic, True = interpret mode,
                  None = defer to the per-backend default at the call site.
                  ``make_plan`` resolves it from the ``lowering`` knob
                  (explicit arg, else DEFAULT_LOWERING) and
                  folds it into ``tuning_key`` so the serving executable
                  cache never aliases lowerings
    pipeline_rounds — sharded round loops run software-pipelined: the O(n)
                  cross-shard combine of round r is issued at the head of
                  round r+1's loop body, next to the (frontier-independent)
                  block decode of the next local sweep, so the collective
                  and the VMEM stream can overlap (one-round epilogue
                  drain).  Bit-identical per lane — only scheduling moves.
                  Algorithms opt in via ``repro.core.plan.round_loop``
    decisions   — the TuningDecision behind this plan's knobs (source
                  'measured' | 'constants', crossover density, table host,
                  resolved ``lowering``) — recorded by make_plan so tests /
                  PSAM accounting can see exactly what ran and why
    """

    mesh: Any = None
    shard_axes: tuple = ()
    backend: str = "auto"
    strategy: str = "auto"
    reduce_mode: str = "flat"
    state_dtype: Any = None
    chunk_blocks: int = DEFAULT_CHUNK_BLOCKS
    dense_frac: float = DEFAULT_DENSE_FRAC
    auto_sparse: str = "sparse"
    dense_frac_batched: float = DEFAULT_DENSE_FRAC
    auto_sparse_batched: str = "sparse"
    batched_flavor_crossover: float | None = None
    interpret: bool | None = None
    pipeline_rounds: bool = False
    decisions: Any = None

    @property
    def axes(self) -> tuple:
        if self.mesh is None:
            return ()
        return tuple(self.shard_axes) or tuple(self.mesh.axis_names)

    @property
    def tuning_key(self) -> tuple:
        """Hashable summary of the knobs that change compiled executables.

        The piece of a compiled-callable cache key that must vary when a
        calibrated table changes a decision — recompiling is correct when
        the strategy / sparse flavor / thresholds changed, and a cache hit
        is correct when they didn't (zero steady-state retraces either
        way).  ``QueryEngine`` and ``ServingService`` fold this into their
        executable cache keys."""
        return (
            self.strategy,
            self.auto_sparse,
            self.auto_sparse_batched,
            None
            if self.batched_flavor_crossover is None
            else float(self.batched_flavor_crossover),
            float(self.dense_frac),
            float(self.dense_frac_batched),
            int(self.chunk_blocks),
            self.interpret,
            bool(self.pipeline_rounds),
        )

    @property
    def num_shards(self) -> int:
        k = 1
        for ax in self.axes:
            k *= self.mesh.shape[ax]
        return k

    @property
    def is_sharded(self) -> bool:
        return self.mesh is not None

    def resolve_mode(self, mode: str | None) -> str:
        """Explicit call-site mode wins; otherwise the plan's strategy."""
        if mode is not None and mode != "auto":
            return mode
        return self.strategy

    def edge_read_words_per_round(self, g) -> int:
        """Large-memory words one dense edgeMap round reads under this plan.

        The planner-owned read quantum the serving scheduler prices
        admission and per-lane drain accounting in: per-shard block reads
        (empty-padding included, compressed backends at compressed byte
        width), summed over this plan's shards — exactly what
        ``PSAMCost.charge_edgemap_planned`` charges for one round.  ``g``
        may be the raw backend or its plan-prepared ``ShardedGraph`` (the
        block split is deterministic, so both price identically).  Delta
        overlays price as their base (``edgemap_round_read_words``'s
        dispatch): patch blocks are DRAM, never part of the read quantum."""
        from .psam import edgemap_round_read_words

        if isinstance(g, ShardedGraph):
            per_shard = edgemap_round_read_words(g.shards, num_shards=1)
            return per_shard * g.num_shards
        return edgemap_round_read_words(g, num_shards=self.num_shards)

    def prepare(self, g, edge_active=None, *, compact_live: bool = False):
        """Shard + stack + place a graph for this plan (identity off-mesh).

        Host-side (concrete arrays only): call once per graph, outside jit,
        like the paper's preprocessing step.  Idempotent on ShardedGraph.

        ``edge_active`` (optional) carries a filter along: any form
        ``edge_active_words`` accepts (GraphFilter, packed words, bool slot
        mask).  When given, returns ``(graph, active)`` with the filter
        words partitioned block-range-wise (``shard_edge_active``) and
        placed next to the edge blocks — off-mesh the pair comes back
        unchanged.  Filters that mutate per round don't need this: the
        sharded executor normalizes raw masks in-trace; ``prepare`` is the
        ahead-of-time placement path for long-lived filters.

        ``compact_live=True`` (requires ``edge_active``) applies
        :func:`compact_live_blocks` **before the shard split**: blocks with
        no active edge under this filter are dropped from the block set
        entirely, so they never occupy a slot in any shard's range and
        never stream — the shards partition the *live* blocks, and the
        returned ``ShardedEdgeActive.live_ids`` records which original
        block each shard row came from.  Off-mesh it returns the compacted
        ``(graph, words)`` pair, the single-device form of the same read
        saving.  Every edgeMap result is unchanged (a dead block only ever
        contributed masked-off slots); only the filter baked in here must
        be the one the rounds run with.
        """
        if compact_live:
            if edge_active is None:
                raise ValueError("compact_live=True requires edge_active")
            if isinstance(g, (ShardedGraph, ShardedEdgeActive)) or isinstance(
                edge_active, ShardedEdgeActive
            ):
                raise ValueError(
                    "compact_live must run before the shard split — pass the "
                    "un-sharded graph and filter"
                )
            orig_nb = g.num_blocks
            g, words, live_ids = compact_live_blocks(g, edge_active)
            edge_active = words
        if not self.is_sharded:
            return g if edge_active is None else (g, edge_active)
        if isinstance(g, ShardedGraph):
            if g.num_shards != self.num_shards:
                raise ValueError(
                    f"graph prepared for {g.num_shards} shards, plan has "
                    f"{self.num_shards}"
                )
            gs = g
        else:
            shards = g.shard(self.num_shards)
            stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *shards)
            sharding = NamedSharding(self.mesh, P(self.axes))
            stacked = jax.tree.map(lambda a: jax.device_put(a, sharding), stacked)
            gs = ShardedGraph(
                shards=stacked,
                num_shards=self.num_shards,
                orig_num_blocks=g.num_blocks,
            )
        if edge_active is None:
            return gs
        if not isinstance(edge_active, ShardedEdgeActive):
            edge_active = shard_edge_active(
                edge_active,
                block_size=gs.block_size,
                blocks_per_shard=gs.blocks_per_shard,
                num_shards=self.num_shards,
                num_blocks=gs.orig_num_blocks,
            )
        if compact_live:
            # audit trail: original block id behind each shard row (pad rows
            # carry the pre-compaction block count, an always-dead sentinel)
            per = gs.blocks_per_shard
            lid = jnp.pad(
                live_ids,
                (0, per * self.num_shards - live_ids.shape[0]),
                constant_values=orig_nb,
            ).reshape(self.num_shards, per)
            edge_active = dataclasses.replace(edge_active, live_ids=lid)
        sharding = NamedSharding(self.mesh, P(self.axes))
        edge_active = ShardedEdgeActive(
            words=jax.device_put(edge_active.words, sharding),
            num_shards=edge_active.num_shards,
            live_ids=edge_active.live_ids,
        )
        return gs, edge_active

    def describe(self) -> str:
        where = (
            f"mesh{tuple(self.mesh.shape[a] for a in self.axes)}"
            if self.is_sharded
            else "single-device"
        )
        d = self.decisions
        extra = (
            ""
            if d is None
            else f" lowering={d.lowering} source={d.source} streamed={d.streamed}"
        )
        return (
            f"plan[{where} backend={self.backend} strategy={self.strategy} "
            f"reduce={self.reduce_mode} shards={self.num_shards}{extra}]"
        )


def _resolve_decision(backend: str, strategy: str, tuning):
    """The TuningDecision behind a plan's knobs.

    ``tuning`` is a :class:`repro.tuning.TuningTable` (always consulted),
    ``"default"`` (the shipped table, consulted for ``strategy="auto"``
    plans only — fixed-strategy plans keep the documented constants unless
    a table is passed explicitly), or ``None``/``"off"`` (static constants).
    Backends the table has no measurements for — including ``"auto"`` when
    no graph was passed — fall back to the constants decision.  So does a
    shipped table measured on another platform or device kind; that
    decision's ``source`` is ``"host-mismatch"``.
    """
    from ..tuning.measure import host_fingerprint
    from ..tuning.table import TuningTable, constants_decision, default_table

    if tuning is None or tuning == "off":
        return constants_decision(backend, strategy)
    if isinstance(tuning, TuningTable):
        return tuning.decide(backend, strategy)
    if tuning == "default":
        if strategy == "auto":
            try:
                table = default_table()
            except (OSError, ValueError):  # missing/stale shipped table
                return constants_decision(backend, strategy)
            if not table.matches_host(host_fingerprint()):
                return dataclasses.replace(
                    constants_decision(backend, strategy),
                    source="host-mismatch",
                    table_host=table.host_key,
                )
            return table.decide(backend, strategy)
        return constants_decision(backend, strategy)
    raise ValueError(
        f"tuning must be a TuningTable, 'default', 'off' or None; got {tuning!r}"
    )


def make_plan(
    g=None,
    *,
    mesh=None,
    strategy: str = "auto",
    shard_axes: tuple = (),
    reduce_mode: str = "flat",
    state_dtype=None,
    chunk_blocks: int | None = None,
    dense_frac: float | None = None,
    lowering: str | None = None,
    pipeline_rounds: bool = False,
    tuning="default",
) -> ExecutionPlan:
    """Build an :class:`ExecutionPlan`, recording the backend from ``g``.

    Knob resolution, most-specific wins: explicit ``chunk_blocks`` /
    ``dense_frac`` arguments → the ``tuning`` source (a calibrated
    :class:`~repro.tuning.TuningTable`, or the shipped default table for
    ``strategy="auto"`` plans) → the static constants in
    ``repro.tuning.defaults``.  The resolved :class:`TuningDecision` —
    including where each value came from (``source='measured'`` vs
    ``'constants'``) and the measured crossover density behind a calibrated
    ``dense_frac`` — is recorded on ``plan.decisions``.  Pass
    ``tuning=None`` (or ``"off"``) to pin the historical constant behavior.

    ``lowering`` picks how every Pallas kernel under the plan lowers:
    ``"native"`` (Mosaic), ``"interpret"`` (XLA interpret mode), or
    ``"auto"`` (per-backend default — native where supported).  ``None``
    defers to ``repro.tuning.defaults.DEFAULT_LOWERING``.  The resolved value lands
    on ``plan.decisions.lowering`` and in ``plan.tuning_key``.

    ``pipeline_rounds=True`` opts sharded round loops into the
    software-pipelined schedule (see :class:`ExecutionPlan` and
    :func:`round_loop`); bit-identical per lane, so it is purely a
    performance knob.
    """
    # kernels depend on core, never the reverse — resolve lazily, exactly
    # like the tuning-table import below
    from ..kernels.lowering import resolve_lowering
    backend = "auto"
    if isinstance(g, ShardedGraph):
        g = g.shards
    if isinstance(g, CompressedCSR):
        backend = "compressed"
    elif isinstance(g, CSRGraph):
        backend = "csr"
    elif hasattr(g, "overlay_small_words"):
        # delta-overlay backend (repro.delta.DeltaGraph) — duck-typed, core
        # never imports delta.  The tuning table has no overlay
        # measurements, so the decision falls back to constants; the
        # recorded backend keeps cost models / benchmarks honest about
        # what ran.  Sharding needs no planner support: DeltaGraph.shard
        # splits base and patch blocks along the same ceil(NB/k) ranges,
        # so prepare()'s stack/device_put path applies unchanged.
        backend = "delta"
    decision = _resolve_decision(backend, strategy, tuning)
    if dense_frac is not None:
        # an explicit threshold pins BOTH predicates — the caller is
        # overriding the crossover, not just the single-query one
        dense_frac_batched = float(dense_frac)
    else:
        dense_frac = decision.dense_frac
        dense_frac_batched = (
            float(decision.dense_frac_batched)
            if decision.dense_frac_batched is not None
            else float(dense_frac)
        )
    if chunk_blocks is None:
        chunk_blocks = decision.chunk_blocks
    resolved_lowering = resolve_lowering(
        lowering if lowering is not None else decision.lowering
    )
    streams = strategy == "sparse_streamed" or (
        strategy == "auto"
        and "sparse_streamed"
        in (decision.auto_sparse, decision.auto_sparse_batched)
    )
    streamed = None
    if streams:
        if isinstance(g, CompressedCSR):
            streamed = (
                "fallback:exception_dense" if exception_dense(g) else "kernel"
            )
        elif g is not None:
            streamed = "fallback:no_decoder"
    decision = dataclasses.replace(
        decision,
        strategy=strategy,
        dense_frac=float(dense_frac),
        dense_frac_batched=dense_frac_batched,
        chunk_blocks=int(chunk_blocks),
        lowering=resolved_lowering,
        streamed=streamed,
    )
    return ExecutionPlan(
        mesh=mesh,
        shard_axes=tuple(shard_axes),
        backend=backend,
        strategy=strategy,
        reduce_mode=reduce_mode,
        state_dtype=state_dtype,
        chunk_blocks=int(chunk_blocks),
        dense_frac=float(dense_frac),
        auto_sparse=decision.auto_sparse,
        dense_frac_batched=dense_frac_batched,
        auto_sparse_batched=decision.auto_sparse_batched,
        batched_flavor_crossover=decision.batched_flavor_crossover,
        interpret=resolved_lowering == "interpret",
        pipeline_rounds=bool(pipeline_rounds),
        decisions=decision,
    )


def sharded_graph_spec(
    n: int,
    num_blocks: int,
    block_size: int,
    num_shards: int,
    weighted: bool = False,
) -> ShardedGraph:
    """ShapeDtypeStruct stand-in for a prepared ShardedGraph (dry-run/AOT)."""
    per, _ = sharded_block_counts(num_blocks, num_shards)
    base = graph_spec(n, per, block_size, weighted)
    stacked = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((num_shards,) + s.shape, s.dtype), base
    )
    return ShardedGraph(
        shards=stacked, num_shards=num_shards, orig_num_blocks=num_blocks
    )


# ----------------------------------------------------------------------
# Sharded executor — the same edgeMap bodies, inside shard_map
# ----------------------------------------------------------------------
def _combine_shards(plan: ExecutionPlan, out, touched, monoid: str, n: int, out_dtype):
    """Monoid-combine per-shard edgeMap outputs: O(n) words per round."""
    with jax.named_scope("sage.shard_combine"):
        return _combine_shards_body(plan, out, touched, monoid, n, out_dtype)


def _combine_shards_body(plan, out, touched, monoid, n, out_dtype):
    axes = plan.axes
    if plan.state_dtype is not None and monoid == "sum":
        out = out.astype(plan.state_dtype)
    if monoid == "sum" and plan.reduce_mode == "hierarchical" and len(axes) > 1:
        if out.ndim > 2:
            raise NotImplementedError("hierarchical reduce: 1-D or (B, n) only")
        # scatter/gather along the VERTEX dim (last axis) — a batched (B, n)
        # output reduce-scatters each lane's row exactly like the 1-D path,
        # so per-lane sums keep the single-query combine order bit for bit
        dim = out.ndim - 1
        fast, slow = axes[-1], axes[:-1]
        k = plan.mesh.shape[fast]
        pad = [(0, 0)] * out.ndim
        pad[dim] = (0, (-n) % k)
        shard = lax.psum_scatter(
            jnp.pad(out, pad), fast, scatter_dimension=dim, tiled=True
        )
        for ax in slow:
            shard = lax.psum(shard, ax)
        out = lax.all_gather(shard, fast, axis=dim, tiled=True)[..., :n]
    elif monoid == "sum":
        for ax in axes:
            out = lax.psum(out, ax)
    elif monoid == "min":
        for ax in axes:
            out = lax.pmin(out, ax)
    elif monoid == "max":
        for ax in axes:
            out = lax.pmax(out, ax)
    elif monoid == "or":
        o = out.astype(jnp.int32)
        for ax in axes:
            o = lax.psum(o, ax)
        out = o > 0
    else:
        raise ValueError(monoid)
    t = touched.astype(jnp.int32)
    for ax in axes:
        t = lax.psum(t, ax)
    if monoid != "or":
        out = out.astype(out_dtype)
    return out, t > 0


def _sharded_edgemap_call(
    plan: ExecutionPlan,
    g,
    frontier,
    x,
    *,
    local_reduce,
    monoid,
    map_fn,
    edge_active,
    mode,
    dense_frac,
    chunk_blocks,
    auto_sparse=None,
    flavor_crossover=None,
    map_lanes=None,
    interpret=None,
):
    """Shared shard/filter plumbing for both sharded executors.

    ``local_reduce`` is the per-shard body — ``edgemap_reduce`` for the
    single-query executor, ``edgemap_reduce_batched`` for the serving path;
    everything else (ShardedEdgeActive validation, in-trace filter-word
    partitioning, shard_map wiring, the monoid combine) is identical and
    lives here exactly once.  ``map_lanes`` (batched executor only) is a
    replicated bool[B] operand selecting which lanes apply ``map_fn`` —
    the cross-op serving rounds carry it through the mesh unchanged."""
    if not isinstance(g, ShardedGraph):
        g = plan.prepare(g)
    mode = plan.resolve_mode(mode)
    dense_frac = plan.dense_frac if dense_frac is None else dense_frac
    chunk_blocks = plan.chunk_blocks if chunk_blocks is None else chunk_blocks
    auto_sparse = plan.auto_sparse if auto_sparse is None else auto_sparse
    interpret = plan.interpret if interpret is None else interpret
    n = g.n
    out_dtype = x.dtype

    active = None
    if edge_active is not None:
        if isinstance(edge_active, ShardedEdgeActive):
            if edge_active.num_shards != plan.num_shards:
                raise ValueError(
                    f"edge_active prepared for {edge_active.num_shards} "
                    f"shards, plan has {plan.num_shards}"
                )
            active = edge_active
        else:
            active = shard_edge_active(
                edge_active,
                block_size=g.block_size,
                blocks_per_shard=g.blocks_per_shard,
                num_shards=plan.num_shards,
                num_blocks=g.orig_num_blocks,
            )

    has_active = active is not None
    has_lanes = map_lanes is not None

    def local(sg, fm, xv, *rest):
        g_local = jax.tree.map(lambda a: a[0], sg.shards)
        kwargs = {} if map_fn is None else {"map_fn": map_fn}
        if flavor_crossover is not None:
            # batched-executor-only knob (edgemap_reduce has no such param)
            kwargs["flavor_crossover"] = flavor_crossover
        rest = list(rest)
        if has_active:
            # shard-local packed filter words, passed through verbatim:
            # every edgeMap consumer normalizes (dense/sparse unpack once,
            # the streamed kernel wants exactly these words — no
            # unpack→repack round trip)
            kwargs["edge_active"] = rest.pop(0).words[0]
        if has_lanes:
            # replicated per-lane map selection (cross-op batching)
            kwargs["map_lanes"] = rest.pop(0)
        out, touched = local_reduce(
            g_local,
            fm,
            xv,
            monoid=monoid,
            mode=mode,
            dense_frac=dense_frac,
            chunk_blocks=chunk_blocks,
            auto_sparse=auto_sparse,
            interpret=interpret,
            **kwargs,
        )
        return _combine_shards(plan, out, touched, monoid, n, out_dtype)

    in_specs = [P(plan.axes), P(), P()]
    operands = [g, frontier, x]
    if has_active:
        in_specs.append(P(plan.axes))
        operands.append(active)
    if has_lanes:
        in_specs.append(P())
        operands.append(map_lanes)
    fn = jax.shard_map(
        local,
        mesh=plan.mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(), P()),
        # the hierarchical all_gather(psum_scatter(...)) is replicated over
        # the fast axis but the static replication check can't prove it
        check_vma=False,
    )
    return fn(*operands)


def sharded_edgemap_reduce(
    plan: ExecutionPlan,
    g,
    frontier_mask: jnp.ndarray,
    x: jnp.ndarray,
    *,
    monoid: str = "min",
    map_fn=None,
    edge_active=None,
    mode: str | None = None,
    dense_frac: float | None = None,
    chunk_blocks: int | None = None,
    auto_sparse: str | None = None,
    interpret: bool | None = None,
):
    """Direction-optimized edgeMap over a mesh: per-shard local pass through
    the ordinary ``edgemap_dense`` / ``edgemap_chunked`` bodies, then one
    monoid combine of the O(n) output.  ``g`` must be a ShardedGraph
    (``plan.prepare``); frontier and vertex state are replicated.

    ``edge_active`` runs plan-native: a ``ShardedEdgeActive`` (from
    ``plan.prepare(g, edge_active=...)``) is consumed as-is; any raw form
    (GraphFilter, packed uint32 words, bool slot mask over the global block
    set) is partitioned in-trace by ``shard_edge_active``.  Each shard's
    packed words ride the mesh at one bit per edge slot and unpack locally
    inside the ``shard_map`` body, so the filtered path shares every line of
    the unfiltered executor."""
    # the executor reuses the single-device bodies; import here so edgemap.py
    # can lazily import this module without a cycle
    from .edgemap import edgemap_reduce

    return _sharded_edgemap_call(
        plan, g, frontier_mask, x,
        local_reduce=edgemap_reduce,
        monoid=monoid, map_fn=map_fn, edge_active=edge_active,
        mode=mode, dense_frac=dense_frac, chunk_blocks=chunk_blocks,
        auto_sparse=auto_sparse, interpret=interpret,
    )


def sharded_edgemap_reduce_batched(
    plan: ExecutionPlan,
    g,
    frontier_masks: jnp.ndarray,
    xb: jnp.ndarray,
    *,
    monoid: str = "min",
    map_fn=None,
    edge_active=None,
    mode: str | None = None,
    dense_frac: float | None = None,
    chunk_blocks: int | None = None,
    auto_sparse: str | None = None,
    map_lanes=None,
    interpret: bool | None = None,
):
    """Batched edgeMap over a mesh: B queries share each shard's one local
    edge sweep, then a single monoid combine moves the O(B·n) output.

    The local body is the single-device ``edgemap_reduce_batched`` run on
    the shard's block set (dense: one shared sweep, one m-row × B-column
    segment reduce; sparse: vmapped chunk loops); frontier rows and vertex
    state are replicated, only the edge blocks (and their packed filter
    words) are partitioned — the same plumbing as the single-query executor
    (``_sharded_edgemap_call``), so cross-shard traffic is O(B·n) words per
    round, never O(m).  ``map_lanes`` (bool[B], replicated) restricts
    ``map_fn`` to the selected lanes exactly as in the single-device
    batched body — heterogeneous (cross-op) serving cohorts run sharded
    with no fallback."""
    from .edgemap import edgemap_reduce_batched

    if auto_sparse is None:
        # batched rounds have their own calibrated sparse flavor (the
        # streamed/plain crossover is B-dependent — see ExecutionPlan)
        auto_sparse = plan.auto_sparse_batched
    if dense_frac is None:
        # ...and their own calibrated Beamer threshold (the batched dense
        # body amortizes one shared sweep over all B lanes)
        dense_frac = plan.dense_frac_batched
    return _sharded_edgemap_call(
        plan, g, frontier_masks, xb,
        local_reduce=edgemap_reduce_batched,
        monoid=monoid, map_fn=map_fn, edge_active=edge_active,
        mode=mode, dense_frac=dense_frac, chunk_blocks=chunk_blocks,
        auto_sparse=auto_sparse,
        flavor_crossover=plan.batched_flavor_crossover,
        map_lanes=map_lanes,
        interpret=interpret,
    )


# ----------------------------------------------------------------------
# Round-pipelined loop driver — overlap combine(r) with sweep(r+1)
# ----------------------------------------------------------------------
# rounds-per-call buckets: traversal diameters on the tested graphs run
# 1..~100; log-spaced to 10k covers pathological chains
_ROUND_BUCKETS = exp_buckets(1.0, 10_000.0, per_decade=6)


def _eager_round_loop_observer(state):
    """Host-side round count of one ``round_loop`` call, or ``None``.

    Counting is only possible when the call executes eagerly — under
    ``jax.jit`` / ``eval_shape`` the state leaves are tracers and the
    "call" is a trace, not an execution — and only wanted when the active
    registry is live.  Returns a ``finish(final, path)`` callable that,
    when the final state carries a scalar integer leaf (BFS's ``rnd``,
    PageRank's ``iters``), records the round count into
    ``sage_round_loop_rounds{path=}`` (reading it waits for the result).
    Device time per round comes from a trace and the ``sage.round`` scope.
    """
    reg = get_registry()
    if not reg.enabled or any(
        isinstance(leaf, jax.core.Tracer) for leaf in jax.tree.leaves(state)
    ):
        return None

    def finish(final, path):
        rounds = [
            int(leaf)
            for leaf in jax.tree.leaves(final)
            if getattr(leaf, "ndim", None) == 0
            and jnp.issubdtype(leaf.dtype, jnp.integer)
        ]
        if rounds:
            reg.histogram(
                "sage_round_loop_rounds",
                "rounds executed per eager round_loop call",
                labels=("path",), buckets=_ROUND_BUCKETS,
            ).observe(float(max(rounds)), path=path)
        return final

    return finish


def round_loop(
    g,
    state,
    *,
    sweep_inputs,
    epilogue,
    cond_fn,
    monoid: str,
    plan: ExecutionPlan | None = None,
    map_fn=None,
    edge_active=None,
    mode: str = "auto",
    batched: bool = False,
):
    """Run a frontier round loop, software-pipelined when the plan asks.

    Every Sage traversal is the same recurrence::

        while cond_fn(state):
            state, frontier, x = sweep_inputs(state)   # pre-sweep mutation
            out, touched = edgeMap(g, frontier, x)     # sweep + combine
            state = epilogue(state, out, touched)

    This driver owns that loop.  For single-device plans (or
    ``plan.pipeline_rounds=False``) it runs the literal sequential
    recurrence above — one ``edgemap_reduce`` (or the batched variant) per
    round, bit-for-bit what the open-coded algorithm loops did.

    For sharded plans with ``pipeline_rounds=True`` the whole loop moves
    inside ONE ``shard_map`` and the schedule is skewed: round ``r``'s
    O(n) cross-shard monoid combine is issued at the *head* of the loop
    body, adjacent to round ``r+1``'s local sweep, so the collective and
    the next block stream overlap (a one-round software pipeline with an
    epilogue drain).  Only scheduling moves — each round still runs
    ``sweep → combine → epilogue`` on the same values in the same order,
    so results are bit-identical per lane to the sequential path (locked
    by ``tests/test_pipeline.py``).

    ``sweep_inputs(state) -> (state', frontier, x)`` may mutate state
    before the sweep (wBFS settles its extracted bucket); ``epilogue(state,
    out, touched) -> state`` applies the combined sweep; ``cond_fn(state)``
    is the loop predicate.  All three must be collective-free — the driver
    owns every cross-shard word.  On the pipelined path they run inside
    ``shard_map``, which refuses arrays they capture from a mesh context:
    such arrays ride in ``state`` instead (see ``pagerank``).
    """
    pipelined = (
        plan is not None and plan.is_sharded and plan.pipeline_rounds
    )
    observe = _eager_round_loop_observer(state)
    if not pipelined:
        from .edgemap import edgemap_reduce, edgemap_reduce_batched

        local_reduce = edgemap_reduce_batched if batched else edgemap_reduce
        kwargs = {} if map_fn is None else {"map_fn": map_fn}
        if edge_active is not None:
            kwargs["edge_active"] = edge_active

        def body(st):
            st, frontier, x = sweep_inputs(st)
            with jax.named_scope("sage.round"):
                out, touched = local_reduce(
                    g, frontier, x, monoid=monoid, mode=mode, plan=plan,
                    **kwargs,
                )
            return epilogue(st, out, touched)

        final = lax.while_loop(cond_fn, body, state)
        return final if observe is None else observe(final, "sequential")

    # ---- pipelined sharded path: the whole loop in one shard_map ----
    if not isinstance(g, ShardedGraph):
        g = plan.prepare(g)
    rmode = plan.resolve_mode(mode)
    chunk_blocks = plan.chunk_blocks
    interpret = plan.interpret
    if batched:
        dense_frac = plan.dense_frac_batched
        auto_sparse = plan.auto_sparse_batched
        flavor_crossover = plan.batched_flavor_crossover
    else:
        dense_frac = plan.dense_frac
        auto_sparse = plan.auto_sparse
        flavor_crossover = None
    n = g.n

    active = None
    if edge_active is not None:
        if isinstance(edge_active, ShardedEdgeActive):
            if edge_active.num_shards != plan.num_shards:
                raise ValueError(
                    f"edge_active prepared for {edge_active.num_shards} "
                    f"shards, plan has {plan.num_shards}"
                )
            active = edge_active
        else:
            active = shard_edge_active(
                edge_active,
                block_size=g.block_size,
                blocks_per_shard=g.blocks_per_shard,
                num_shards=plan.num_shards,
                num_blocks=g.orig_num_blocks,
            )
    has_active = active is not None

    from .edgemap import edgemap_reduce, edgemap_reduce_batched

    local_reduce = edgemap_reduce_batched if batched else edgemap_reduce

    def local_sweep(sg, frontier, x, *rest):
        # local (uncombined) sweep — same body the sequential sharded
        # executor runs per shard, resolved from the same plan knobs
        g_local = jax.tree.map(lambda a: a[0], sg.shards)
        kwargs = {} if map_fn is None else {"map_fn": map_fn}
        if batched and flavor_crossover is not None:
            kwargs["flavor_crossover"] = flavor_crossover
        if has_active:
            kwargs["edge_active"] = rest[0].words[0]
        with jax.named_scope("sage.round.sweep"):
            return local_reduce(
                g_local, frontier, x, monoid=monoid, mode=rmode,
                dense_frac=dense_frac, chunk_blocks=chunk_blocks,
                auto_sparse=auto_sparse, interpret=interpret, **kwargs,
            )

    # shape probes run outside the shard_map body: under its manual mesh,
    # eval_shape would re-trace the callbacks against the auto mesh
    rest_ops = [active] if has_active else []
    out_dtype = jax.eval_shape(lambda s: sweep_inputs(s)[2], state).dtype
    pending_shapes = jax.eval_shape(
        lambda sg, s, *rest: local_sweep(sg, *sweep_inputs(s)[1:], *rest),
        g, state, *rest_ops,
    )

    def whole(sg, st0, *rest):
        def sweep(frontier, x):
            return local_sweep(sg, frontier, x, *rest)

        def combine(pending):
            out, touched = pending
            return _combine_shards(plan, out, touched, monoid, n, out_dtype)

        def maybe_sweep(st, pending, flag):
            # the local sweep is collective-free, so it is legal under
            # lax.cond inside shard_map; the combine is NOT conditional
            def do(st, pending):
                st2, frontier, x = sweep_inputs(st)
                return st2, sweep(frontier, x)

            def dont(st, pending):
                return st, pending

            return lax.cond(flag, do, dont, st, pending)

        zeros = jax.tree.map(
            lambda sd: jnp.zeros(sd.shape, sd.dtype), pending_shapes
        )

        flag0 = cond_fn(st0)
        st1, pending0 = maybe_sweep(st0, zeros, flag0)

        def body(carry):
            st, pending, flag = carry
            # head-of-round: combine round r while the hardware can overlap
            # it with round r+1's (already issued) local block stream
            out, touched = combine(pending)
            st = epilogue(st, out, touched)
            flag = cond_fn(st)
            st, pending = maybe_sweep(st, pending, flag)
            return st, pending, flag

        final, _, _ = lax.while_loop(lambda c: c[2], body, (st1, pending0, flag0))
        return final

    in_specs = [P(plan.axes), P()]
    operands = [g, state]
    if has_active:
        in_specs.append(P(plan.axes))
        operands.append(active)
    fn = jax.shard_map(
        whole,
        mesh=plan.mesh,
        in_specs=tuple(in_specs),
        out_specs=P(),
        # every shard computes the same replicated state (the combine is
        # replicated by construction) but the static check can't prove it
        check_vma=False,
    )
    final = fn(*operands)
    return final if observe is None else observe(final, "pipelined")
