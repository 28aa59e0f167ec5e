"""Tuning-owned constants — the single seam every hand-tuned knob sits behind.

Every threshold in the hot path that used to be a scattered literal lives
here exactly once, so a measured :class:`~repro.tuning.TuningTable` can
override it through one well-known name and the code that consumes the knob
never needs to know whether the value was hand-picked or calibrated:

* ``DEFAULT_DENSE_FRAC``   — the Beamer direction-optimization threshold
  (dense when the frontier's incident edges exceed ``m / dense_frac``).
  Previously defaulted independently in ``core/plan.py`` and twice in
  ``core/edgemap.py``; a calibrated plan replaces it with ``1 / d*`` for
  the measured dense/sparse crossover density ``d*``.
* ``DEFAULT_CHUNK_BLOCKS`` — EDGEMAPCHUNKED chunk-pool size (blocks per
  chunk-loop iteration; the paper's thread-local pool, App. A).
* ``DEFAULT_TILE_BLOCKS``  — TB, the scalar-prefetched live-id tile of the
  frontier-sparse Pallas kernel (blocks per ``PrefetchScalarGridSpec``
  launch, ``repro.kernels.compressed_spmv``).
* ``DEFAULT_MAX_BATCH``    — serving batch width cap (``QueryEngine`` /
  ``ServingService``); calibration replaces it with the knee of the
  measured per-query cost curve over B.
* ``DEFAULT_EST_ROUNDS``   — the cold-start admission estimate (rounds per
  request) the serving ledger prices reservations with until per-op
  observed round counts warm up.
* ``DEFAULT_COMPACT_HYSTERESIS`` / ``DEFAULT_OVERLAY_COST_SCALE`` — the
  constants behind :class:`repro.tuning.OverlayTrigger`: compact a delta
  overlay once its accumulated per-sweep small-op surcharge (scaled by
  the cost-scale calibration) exceeds ``hysteresis × ω × write_words`` —
  i.e. once queries have already paid more in overlay overhead than one
  compaction would cost.  ``measured_overlay_trigger`` replaces the cost
  scale with a timed dense-sweep ratio.
* ``DEFAULT_EDITS_PER_COMPACT`` — the cold-start admission amortization
  horizon for edits: one edit is priced at ``ω × write_words / horizon``
  until the service has observed real edits-per-compaction counts.
* ``DEFAULT_LOWERING``     — how the Pallas kernels lower: ``"auto"``
  resolves per backend at plan time (native Mosaic on TPU, XLA interpret
  mode elsewhere); ``"native"`` / ``"interpret"`` force one side.  A
  calibrated table replaces ``"auto"`` with the measured winner.
* ``HARDWARE_PEAKS``       — published per-chip peaks keyed by
  ``jax.Device.device_kind``: bf16 FLOP/s, HBM bandwidth and capacity,
  per-link ICI bandwidth.  ``repro.tuning.hardware_model`` is the one
  reader; a device kind missing here is an error, never a default.

This module is import-light on purpose (no jax, no numpy): ``repro.core``
imports it at module load, so it must never import back into core.
"""
from __future__ import annotations

DEFAULT_DENSE_FRAC = 20
DEFAULT_CHUNK_BLOCKS = 256
DEFAULT_TILE_BLOCKS = 8
DEFAULT_MAX_BATCH = 8
DEFAULT_EST_ROUNDS = 8
DEFAULT_LOWERING = "auto"
DEFAULT_COMPACT_HYSTERESIS = 1.0
DEFAULT_OVERLAY_COST_SCALE = 1.0
DEFAULT_EDITS_PER_COMPACT = 1024

# Source: Google Cloud documentation, "TPU v5e" — per chip 197 TFLOP/s
# bf16, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
# interconnect (four 50 GB/s links; a collective hop is priced at one).
HARDWARE_PEAKS = {
    "TPU v5 lite": {
        "peak_flops": 197e12,
        "hbm_bw": 819e9,
        "hbm_bytes": 16e9,
        "ici_bw": 50e9,
    },
}
