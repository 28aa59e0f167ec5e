"""repro.obs — the observability layer: metrics registry + tracing.

Every runtime layer reports here (ISSUE 9):

* :class:`Registry` — labeled counters, gauges and fixed-bucket histograms
  with exact p50/p99 extraction; ``snapshot()`` (nested dict) and
  ``to_prometheus_text()`` (text exposition format) for pull-model export.
* :func:`get_registry` / :func:`set_registry` / :func:`use_registry` —
  the process-global default registry plus injectable instances;
  :func:`noop_registry` installs disabled mode (one attribute lookup per
  hot-path record, bit-identical results, no clock reads).
* :func:`trace_session` / :func:`annotate` / :func:`ingest_span` —
  ``jax.profiler`` capture as a context manager, usable from serving, and
  named host spans; the device work it captures carries the engine's named
  scopes (``sage.round``, ``sage.round.sweep``, ``sage.sweep.dense``,
  ``sage.sweep.sparse``, ``sage.decode``, ``sage.shard_combine``; see
  ``repro.obs.trace``).
* ``python -m repro.obs.dump`` — run a small instrumented serving replay
  (or nothing) and print the registry as Prometheus text or JSON.

What reports where:

* ``ServingService`` — per-(op, tenant) latency histograms, queue depth,
  flush causes (deadline/depth/forced), admission outcomes, occupancy,
  and the PSAM-model-vs-wall-clock drift gauge
  (``sage_psam_drift_words_per_second``).
* ``QueryEngine`` — batch-size histograms, lane/padding counters,
  compile-cache hits/misses (steady-state retraces are a *metric*).
* ``repro.core.plan`` — rounds per eager ``round_loop`` call
  (``sage_round_loop_rounds``).
* ``repro.core.csr.build_csr`` / ``repro.core.compressed.compress`` — the
  seconds of each phase of the last ingest
  (``sage_ingest_last_seconds{phase=dedupe|layout|fetch|encode}``).
* ``PSAMCost`` — every ``charge_*`` mirrored into
  ``sage_psam_*_words_total{charge=...}`` counters.
* ``repro.delta`` + the mutable serving path — applied edits by kind
  (``sage_delta_edits_total``), live overlay size gauges
  (``sage_delta_patch_edges`` / ``sage_delta_tombstones`` /
  ``sage_delta_overlay_small_words``), and compaction telemetry
  (``sage_delta_compactions_total``,
  ``sage_delta_last_compact_write_words``).

See ``docs/observability.md`` for the metric catalogue and a scrape
example.
"""
from .metrics import (
    DEFAULT_LATENCY_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    NoopRegistry,
    Registry,
    exp_buckets,
    get_registry,
    noop_registry,
    set_registry,
    use_registry,
)
from .trace import annotate, ingest_span, trace_session

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "Registry",
    "NoopRegistry",
    "exp_buckets",
    "get_registry",
    "set_registry",
    "use_registry",
    "noop_registry",
    "DEFAULT_LATENCY_BUCKETS",
    "trace_session",
    "annotate",
    "ingest_span",
]
