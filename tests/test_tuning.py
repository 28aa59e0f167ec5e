"""Measured-cost autotuner: table persistence, crossover math, plan
decisions, auto-vs-explicit parity, executable-cache keys, admission EWMA.

The tuning contracts (ISSUE 7):

* **Persistence** — TuningTable round-trips through JSON bit-for-bit;
  stale ``schema_version`` and missing keys are rejected loudly, never
  silently reinterpreted.
* **Crossover math** — the dense/sparse and streamed/plain flips are
  log-density-interpolated from hand-built sweeps, clamped at degenerate
  sweeps; lookups interpolate and end-clamp.
* **Plan decisions** — ``make_plan(strategy="auto")`` consults the table
  and records a ``TuningDecision`` (source, crossover, host) on the plan;
  explicit knob arguments always win; ``tuning=None`` pins the constants.
* **Parity** — auto is bit-identical to the explicit strategy it selects,
  single and batched (B ∈ {1, 8}), both backends, meshes {1, 2, 4}
  (subprocess leg) — tuning changes WHICH body runs, never what it
  computes.
* **Serving** — the engine's executable cache key includes the plan's
  ``tuning_key`` (zero steady-state retraces, distinct keys per decision);
  ``max_batch`` sizes from the table; admission prices cold requests at
  the flat ``est_rounds`` and warm ones at EWMA-settled observed rounds.
"""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compress, edgemap_reduce, edgemap_reduce_batched, make_plan
from repro.core.plan import ExecutionPlan
from repro.data import rmat_graph
from repro.serving import QueryEngine, ServiceConfig, ServingService
from repro.tuning import (
    DEFAULT_CHUNK_BLOCKS,
    DEFAULT_DENSE_FRAC,
    DEFAULT_EST_ROUNDS,
    DEFAULT_MAX_BATCH,
    SCHEMA_VERSION,
    TuningTable,
    constants_decision,
    crossover_from_sweep,
    default_table,
    dense_frac_from_crossover,
    flavor_crossover_from_sweep,
    hardware_model,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str) -> str:
    r = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        env={**os.environ, "PYTHONPATH": "src"},
        cwd=ROOT,
        timeout=420,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


def _sweep():
    # dense loses at low density, wins above ~0.3 (sign change in the
    # middle interval)
    return [
        {"density": 0.01, "dense_us": 100.0, "sparse_us": 10.0},
        {"density": 0.1, "dense_us": 100.0, "sparse_us": 60.0},
        {"density": 1.0, "dense_us": 100.0, "sparse_us": 500.0},
    ]


def _table_data(**over):
    entry = {
        "density_sweep": _sweep(),
        "crossover_density": crossover_from_sweep(_sweep()),
        "dense_frac": dense_frac_from_crossover(crossover_from_sweep(_sweep())),
        "chunk_blocks": 64,
        "auto_sparse": "sparse",
        "max_batch": 4,
        **over,
    }
    return {
        "schema_version": SCHEMA_VERSION,
        "host": {"platform": "cpu", "device_kind": "testhost"},
        "hardware": {"peak_flops": 1e12, "hbm_bw": 1e9, "ici_bw": 1e8},
        "backends": {"csr": entry},
    }


# ----------------------------------------------------------------------
# Persistence: JSON round-trip + schema rejection
# ----------------------------------------------------------------------
def test_table_json_roundtrip(tmp_path):
    t = TuningTable.from_dict(_table_data())
    again = TuningTable.loads(t.dumps())
    assert again.to_dict() == t.to_dict()
    path = tmp_path / "table.json"
    t.save(str(path))
    loaded = TuningTable.load(str(path))
    assert loaded.to_dict() == t.to_dict()
    assert loaded.host_key == "cpu/testhost"
    assert loaded.backends() == ["csr"]


def test_stale_schema_rejected(tmp_path):
    data = _table_data()
    data["schema_version"] = SCHEMA_VERSION + 1
    with pytest.raises(ValueError, match="schema_version"):
        TuningTable.from_dict(data)
    path = tmp_path / "stale.json"
    path.write_text(json.dumps(data))
    with pytest.raises(ValueError, match="schema_version"):
        TuningTable.load(str(path))


def test_missing_keys_rejected():
    data = _table_data()
    del data["backends"]["csr"]["dense_frac"]
    with pytest.raises(ValueError, match="missing keys"):
        TuningTable.from_dict(data)
    with pytest.raises(ValueError, match="missing keys"):
        TuningTable.from_dict({"schema_version": SCHEMA_VERSION})
    bad = _table_data()
    bad["backends"]["csr"]["density_sweep"] = []
    with pytest.raises(ValueError, match="empty density sweep"):
        TuningTable.from_dict(bad)


# ----------------------------------------------------------------------
# Crossover math + interpolating lookup
# ----------------------------------------------------------------------
def test_crossover_interpolation_and_clamps():
    d = crossover_from_sweep(_sweep())
    assert 0.1 < d < 1.0  # flips in the top interval
    assert dense_frac_from_crossover(d) == pytest.approx(1.0 / d)
    # dense cheaper everywhere -> lowest measured density
    all_dense = [{"density": x, "dense_us": 1.0, "sparse_us": 9.0} for x in (0.01, 1.0)]
    assert crossover_from_sweep(all_dense) == 0.01
    # sparse cheaper everywhere -> 1.0 (never dense)
    all_sparse = [{"density": x, "dense_us": 9.0, "sparse_us": 1.0} for x in (0.01, 1.0)]
    assert crossover_from_sweep(all_sparse) == 1.0
    assert dense_frac_from_crossover(1e-9) == 1e4  # clamped
    assert dense_frac_from_crossover(2.0) == 1.0


def test_flavor_crossover_from_sweep():
    rows = [
        {"density": 0.001, "sparse_us": 40.0, "sparse_streamed_us": 10.0},
        {"density": 0.05, "sparse_us": 30.0, "sparse_streamed_us": 35.0},
    ]
    d = flavor_crossover_from_sweep(rows)
    assert 0.001 < d < 0.05  # streamed wins below, plain above
    assert flavor_crossover_from_sweep([{"density": 0.01, "sparse_us": 1.0}]) is None
    plain = [{"density": 0.01, "sparse_us": 1.0, "sparse_streamed_us": 2.0}]
    assert flavor_crossover_from_sweep(plain) == 0.0
    streamed = [{"density": 0.01, "sparse_us": 2.0, "sparse_streamed_us": 1.0}]
    assert flavor_crossover_from_sweep(streamed) == 1.0


def test_strategy_us_interpolates_and_clamps():
    t = TuningTable.from_dict(_table_data())
    assert t.strategy_us("csr", "sparse", 1e-5) == 10.0  # end-clamped
    assert t.strategy_us("csr", "sparse", 5.0) == 500.0
    mid = t.strategy_us("csr", "sparse", 0.0316)  # log-midpoint of 0.01, 0.1
    assert mid == pytest.approx(35.0, rel=1e-3)
    assert t.best_strategy("csr", 0.01) == "sparse"
    assert t.best_strategy("csr", 1.0) == "dense"
    with pytest.raises(KeyError):
        t.strategy_us("compressed", "sparse", 0.1)


# ----------------------------------------------------------------------
# Plan decisions: table -> plan knobs, source recorded, overrides win
# ----------------------------------------------------------------------
def test_make_plan_records_measured_decision():
    g = rmat_graph(64, 256, seed=5, block_size=32)
    t = TuningTable.from_dict(_table_data())
    plan = make_plan(g, tuning=t)
    d = plan.decisions
    assert d.source == "measured" and d.table_host == "cpu/testhost"
    assert plan.dense_frac == t.dense_frac("csr") == d.dense_frac
    assert plan.chunk_blocks == 64
    assert d.crossover_density == pytest.approx(t.crossover_density("csr"))
    # the batched threshold falls back to the single-query one when the
    # table has no batched sweep
    assert plan.dense_frac_batched == plan.dense_frac
    # unmeasured backend -> constants decision
    cplan = make_plan(compress(g), tuning=t)
    assert cplan.decisions.source == "constants"
    assert cplan.dense_frac == DEFAULT_DENSE_FRAC


def test_make_plan_explicit_args_beat_table():
    g = rmat_graph(64, 256, seed=5, block_size=32)
    t = TuningTable.from_dict(_table_data())
    plan = make_plan(g, tuning=t, dense_frac=7.0, chunk_blocks=32)
    assert plan.dense_frac == 7.0 and plan.chunk_blocks == 32
    assert plan.dense_frac_batched == 7.0  # explicit pins both predicates
    assert plan.decisions.dense_frac == 7.0
    off = make_plan(g, tuning=None)
    assert off.decisions.source == "constants"
    assert off.dense_frac == DEFAULT_DENSE_FRAC
    assert off.chunk_blocks == DEFAULT_CHUNK_BLOCKS
    with pytest.raises(ValueError, match="tuning"):
        make_plan(g, tuning="bogus")


def test_default_table_ships_and_plans_measured():
    t = default_table()
    assert t.schema_version == SCHEMA_VERSION
    assert set(t.backends()) >= {"csr", "compressed"}
    g = rmat_graph(64, 256, seed=5, block_size=32)
    for backend in (g, compress(g)):
        plan = make_plan(backend)
        assert plan.decisions.source == "measured"
        assert plan.decisions.table_host == t.host_key
        # the calibrated knobs reach the plan AND its cache-key summary
        assert plan.tuning_key[4] == plan.dense_frac
        assert plan.dense_frac == t.dense_frac(plan.backend)
    # the hardware model is the published peaks of a known device kind
    hw = hardware_model("TPU v5 lite")
    assert set(hw) >= {"peak_flops", "hbm_bw", "ici_bw"}
    with pytest.raises(KeyError):
        hardware_model("no such chip")


def test_constants_decision_matches_defaults():
    d = constants_decision("csr")
    assert d.source == "constants"
    assert d.dense_frac == DEFAULT_DENSE_FRAC
    assert d.chunk_blocks == DEFAULT_CHUNK_BLOCKS
    assert d.max_batch == DEFAULT_MAX_BATCH
    # the plan dataclass defaults are the same single source of truth
    p = ExecutionPlan()
    assert p.dense_frac == DEFAULT_DENSE_FRAC
    assert p.chunk_blocks == DEFAULT_CHUNK_BLOCKS


# ----------------------------------------------------------------------
# Parity: auto == the explicit strategy it selects, single + batched
# ----------------------------------------------------------------------
@pytest.mark.parametrize("compressed", [False, True], ids=["csr", "compressed"])
def test_auto_bit_identical_to_selected_strategy(compressed):
    g = rmat_graph(128, 512, seed=11, block_size=32)
    backend = compress(g) if compressed else g
    plan = make_plan(backend)  # shipped measured table
    x0 = jnp.arange(backend.n, dtype=jnp.float32)
    deg = np.asarray(backend.degrees)
    for frac, seed in [(0.01, 0), (1.0, 1)]:
        rng = np.random.default_rng(seed)
        mask_np = np.zeros(backend.n, bool)
        k = max(1, int(frac * backend.n))
        mask_np[rng.choice(backend.n, size=k, replace=False)] = True
        mask = jnp.asarray(mask_np)
        # the strategy auto's predicate selects at this density
        want_mode = (
            "dense"
            if float(mask_np @ deg) * plan.dense_frac > backend.m
            else plan.auto_sparse
        )
        got = edgemap_reduce(backend, mask, x0, monoid="min", plan=plan)
        want = edgemap_reduce(backend, mask, x0, monoid="min", mode=want_mode,
                              chunk_blocks=plan.chunk_blocks)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("B", [1, 8])
@pytest.mark.parametrize("compressed", [False, True], ids=["csr", "compressed"])
def test_batched_auto_bit_identical(compressed, B):
    g = rmat_graph(128, 512, seed=13, block_size=32)
    backend = compress(g) if compressed else g
    plan = make_plan(backend)
    xb = jnp.broadcast_to(
        jnp.arange(backend.n, dtype=jnp.float32)[None, :], (B, backend.n)
    )
    deg = np.asarray(backend.degrees)
    for frac, seed in [(0.01, 0), (1.0, 1)]:
        rng = np.random.default_rng(seed)
        masks_np = np.zeros((B, backend.n), bool)
        k = max(1, int(frac * backend.n))
        for i in range(B):
            masks_np[i, rng.choice(backend.n, size=k, replace=False)] = True
        masks = jnp.asarray(masks_np)
        # all lanes share the density, so batched auto runs one branch:
        # the batched-calibrated threshold and sparse flavor decide it
        dense_lane = float(masks_np[0] @ deg) * plan.dense_frac_batched > backend.m
        want_mode = "dense" if dense_lane else plan.auto_sparse_batched
        got = edgemap_reduce_batched(backend, masks, xb, monoid="min", plan=plan)
        want = edgemap_reduce_batched(
            backend, masks, xb, monoid="min", mode=want_mode,
            chunk_blocks=plan.chunk_blocks,
        )
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_auto_parity_on_meshes_subprocess():
    """Auto under measured tuning == untuned single-device truth, for mesh
    sizes {1, 2, 4} x both backends x B in {1, 8}."""
    out = _run(
        r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.compat import make_mesh, use_mesh
from repro.data import rmat_graph
from repro.core import compress, make_plan, edgemap_reduce, edgemap_reduce_batched

g = rmat_graph(128, 512, seed=17, block_size=32)
c = compress(g)
n = g.n
x0 = jnp.arange(n, dtype=jnp.float32)
rng = np.random.default_rng(0)
mask_np = rng.random(n) < 0.05
mask = jnp.asarray(mask_np)
want = edgemap_reduce(g, mask, x0, monoid="min", mode="sparse")
for B in (1, 8):
    masks = jnp.broadcast_to(mask[None, :], (B, n))
    xb = jnp.broadcast_to(x0[None, :], (B, n))
    want_b = edgemap_reduce_batched(g, masks, xb, monoid="min", mode="sparse")
    for shape in [(1,), (2,), (4,)]:
        mesh = make_mesh(shape, ("data",))
        for backend in (g, c):
            plan = make_plan(backend, mesh=mesh)
            assert plan.decisions.source == "measured", plan.decisions
            gs = plan.prepare(backend)
            with use_mesh(mesh):
                out = edgemap_reduce(gs, mask, x0, monoid="min", plan=plan)
                out_b = edgemap_reduce_batched(gs, masks, xb, monoid="min", plan=plan)
            name = (B, shape, type(backend).__name__)
            for a, b in zip(out, want):
                assert np.array_equal(np.asarray(a), np.asarray(b)), name
            for a, b in zip(out_b, want_b):
                assert np.array_equal(np.asarray(a), np.asarray(b)), name
print("OK")
"""
    )
    assert "OK" in out


# ----------------------------------------------------------------------
# Serving: cache keys, max_batch sizing, admission EWMA
# ----------------------------------------------------------------------
def test_engine_cache_key_includes_tuning_and_never_retraces():
    g = rmat_graph(128, 512, seed=7, block_size=32)
    plan = make_plan(g)
    eng = QueryEngine(g, plan=plan)
    for _ in range(3):  # steady state: same decision -> zero retraces
        eng.submit("bfs", src=0)
        eng.submit("bfs", src=3)
        eng.flush()
    assert all(v == 1 for v in eng.trace_counts.values())
    assert all(k[2] == plan.tuning_key for k in eng.trace_counts)
    # a different tuning decision is a different executable cache key
    plan2 = make_plan(g, tuning=None)
    assert plan2.tuning_key != plan.tuning_key
    eng2 = QueryEngine(g, plan=plan2)
    eng2.submit("bfs", src=0)
    eng2.flush()
    assert all(k[2] == plan2.tuning_key for k in eng2.trace_counts)


def test_engine_max_batch_sized_from_table():
    g = rmat_graph(128, 512, seed=7, block_size=32)
    t = TuningTable.from_dict(_table_data())  # max_batch = 4
    plan = make_plan(g, tuning=t)
    assert plan.decisions.max_batch == 4
    assert QueryEngine(g, plan=plan).max_batch == 4
    assert QueryEngine(g, plan=plan, max_batch=2).max_batch == 2  # arg wins
    # a measured plan carries the table's knee; plan-less engines and
    # constants-only plans stay at the static default
    assert make_plan(g).decisions.max_batch == default_table().max_batch("csr")
    assert QueryEngine(g).max_batch == DEFAULT_MAX_BATCH
    assert QueryEngine(g, plan=make_plan(g, tuning=None)).max_batch == (
        DEFAULT_MAX_BATCH
    )


def test_admission_prices_cold_flat_and_warm_ewma():
    g = rmat_graph(128, 512, seed=7, block_size=32)
    svc = ServingService(g, config=ServiceConfig(slo=0.05))
    cold = svc._estimate_words("bfs")
    assert cold == pytest.approx(
        svc._round_words * DEFAULT_EST_ROUNDS / svc.max_batch
    )
    t = svc.submit("bfs", src=0, now=0.0)
    assert t.est_words == pytest.approx(cold)
    svc.tick(0.06)  # drain: rounds observed, EWMA seeded
    key = ("bfs", svc.engine._backend_key)
    assert key in svc.observed_rounds
    warm = svc._estimate_words("bfs")
    assert warm == pytest.approx(
        svc._round_words * svc.observed_rounds[key] / svc.max_batch
    )
    assert warm != pytest.approx(cold)  # a real BFS is not 8 rounds deep
    # EWMA: a second identical drain keeps the settled value stable
    before = svc.observed_rounds[key]
    svc.submit("bfs", src=0, now=1.0)
    svc.tick(1.06)
    after = svc.observed_rounds[key]
    assert after == pytest.approx(before, rel=0.5)
    # ...and an unseen op is still priced flat
    assert svc._estimate_words("wbfs") == pytest.approx(cold)
