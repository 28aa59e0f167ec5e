"""repro.tuning — measured-cost calibration for every hand-tuned threshold.

The PSAM's analytic constants assume a fixed read/write asymmetry; real
devices don't (Optane characterization, arXiv:1904.07162).  This package
replaces assumption with measurement:

  calibrate                — microbenchmark per-strategy edgeMap cost on
                             this host (density grid × backend × chunk /
                             batch / tile knobs) and return a TuningTable
  TuningTable              — versioned, host-keyed, schema-checked JSON
                             store with interpolating density lookups
  TuningDecision           — the knob values one ExecutionPlan executes,
                             recorded on every plan (``plan.decisions``)
  default_table            — the shipped offline table (cold-start path)
  load_table               — load a calibrated table (or the default)
  constants_decision       — the static-defaults decision (un-tuned plans)
  hardware_model           — published per-chip peaks (FLOP/s, HBM and
                             ICI bandwidth) keyed by device kind
  crossover_from_sweep     — density where dense becomes cheaper, from
                             measured sweep rows
  dense_frac_from_crossover— Beamer threshold equivalent of a crossover
  flavor_crossover_from_sweep — density where the batched streamed union
                             stops beating vmapped plain sparse
  OverlayTrigger           — delta-overlay compaction policy (compact once
                             the accumulated sweep surcharge covers the
                             ω write); constants_overlay_trigger is the
                             static-defaults instance,
                             measured_overlay_trigger calibrates the
                             overlay cost scale from timed sweeps
  SCHEMA_VERSION           — current table schema (stale tables rejected)

plus the static defaults (``DEFAULT_DENSE_FRAC``, ``DEFAULT_CHUNK_BLOCKS``,
``DEFAULT_TILE_BLOCKS``, ``DEFAULT_MAX_BATCH``, ``DEFAULT_EST_ROUNDS``,
``DEFAULT_LOWERING``, ``HARDWARE_PEAKS``) — module-level constants documented in
``repro.tuning.defaults``.

CLI: ``python -m repro.tuning --quick --out table.json`` (the nightly job).

Import discipline: ``repro.core`` reads ``repro.tuning.defaults`` and (at
plan-build time) ``default_table()``; nothing in this package imports
``repro.core`` at module load — ``measure`` pulls it in lazily inside the
calibration functions.
"""
from .defaults import (
    DEFAULT_CHUNK_BLOCKS,
    DEFAULT_DENSE_FRAC,
    DEFAULT_EST_ROUNDS,
    DEFAULT_LOWERING,
    DEFAULT_MAX_BATCH,
    DEFAULT_TILE_BLOCKS,
    HARDWARE_PEAKS,
)
from .measure import calibrate, host_fingerprint
from .overlay import (
    OverlayTrigger,
    constants_overlay_trigger,
    measured_overlay_trigger,
)
from .table import (
    SCHEMA_VERSION,
    TuningDecision,
    TuningTable,
    constants_decision,
    crossover_from_sweep,
    default_table,
    dense_frac_from_crossover,
    flavor_crossover_from_sweep,
    hardware_model,
    load_table,
)

__all__ = [
    "SCHEMA_VERSION",
    "DEFAULT_DENSE_FRAC",
    "DEFAULT_CHUNK_BLOCKS",
    "DEFAULT_TILE_BLOCKS",
    "DEFAULT_MAX_BATCH",
    "DEFAULT_EST_ROUNDS",
    "DEFAULT_LOWERING",
    "HARDWARE_PEAKS",
    "OverlayTrigger",
    "TuningTable",
    "TuningDecision",
    "calibrate",
    "constants_decision",
    "constants_overlay_trigger",
    "crossover_from_sweep",
    "default_table",
    "dense_frac_from_crossover",
    "flavor_crossover_from_sweep",
    "hardware_model",
    "host_fingerprint",
    "load_table",
    "measured_overlay_trigger",
]
