"""Observability layer (ISSUE 9): registry math, exact PSAM mirroring,
and the locked contract that instrumentation NEVER changes results.

Four contract groups:

* **Registry semantics** — get-or-create idempotence, schema-mismatch
  rejection, label filtering, gauge-NaN-when-unset, prefix reset, and the
  two exposition formats (snapshot dict, Prometheus text).
* **Histogram extraction** — bucket-walk p50/p99 pinned against
  ``numpy.quantile`` to within one bucket's relative width, across
  lognormal/uniform/single-sample shapes.
* **Exact mirroring** — every ``PSAMCost.charge_*`` lands word-for-word in
  the ``sage_psam_*_words_total`` counters; the engine's cache hit/miss
  counters ARE the zero-steady-state-retrace contract.
* **Bit-exactness** — dense / sparse_streamed / pipelined plans, meshes
  {1, 2, 4}, batch widths {1, 8}: identical results under an enabled
  registry and under ``noop_registry()`` (mesh > 1 runs in a subprocess
  with fake CPU devices, like the rest of the mesh suite).
"""
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import make_plan
from repro.core.psam import PSAMCost
from repro.data import rmat_graph
from repro.obs import (
    Registry,
    exp_buckets,
    get_registry,
    noop_registry,
    use_registry,
)
from repro.serving import QueryEngine, ServiceConfig, ServingService

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


def _graph(weighted=True):
    return rmat_graph(256, 1024, weighted=weighted, seed=3, block_size=32)


# ----------------------------------------------------------------------
# Registry semantics
# ----------------------------------------------------------------------
def test_counter_get_or_create_and_labels():
    reg = Registry()
    c = reg.counter("t_total", "help", labels=("op",))
    assert reg.counter("t_total", labels=("op",)) is c
    c.inc(op="bfs")
    c.inc(2, op="bfs")
    c.inc(5, op="wbfs")
    assert c.value(op="bfs") == 3
    assert c.value(op="wbfs") == 5
    assert c.value() == 8  # no filter aggregates every series
    with pytest.raises(ValueError):
        c.inc(-1, op="bfs")
    with pytest.raises(ValueError):
        c.inc()  # missing the declared label
    with pytest.raises(ValueError):
        reg.gauge("t_total")  # kind mismatch on an existing name
    with pytest.raises(ValueError):
        reg.counter("t_total", labels=("tenant",))  # label mismatch


def test_gauge_nan_when_unset():
    reg = Registry()
    ga = reg.gauge("t_g")
    assert math.isnan(ga.value())
    ga.set(2.5)
    ga.add(-1.0)
    assert ga.value() == 1.5


def test_registry_prefix_reset():
    reg = Registry()
    reg.counter("sage_engine_x_total").inc(4)
    reg.counter("sage_service_y_total").inc(7)
    reg.reset(prefix="sage_engine_")
    assert reg.counter("sage_engine_x_total").value() == 0
    assert reg.counter("sage_service_y_total").value() == 7
    reg.reset()
    assert reg.counter("sage_service_y_total").value() == 0


def test_snapshot_and_prometheus_text():
    reg = Registry()
    reg.counter("t_total", "a counter", labels=("op",)).inc(3, op="bfs")
    h = reg.histogram("t_sec", "a hist", buckets=(1.0, 2.0, 4.0))
    h.observe(0.5)
    h.observe(3.0)
    snap = reg.snapshot()
    assert snap["t_total"]["series"]["bfs"] == 3
    hs = snap["t_sec"]["series"][""]
    assert hs["count"] == 2 and hs["sum"] == 3.5
    assert hs["min"] == 0.5 and hs["max"] == 3.0
    text = reg.to_prometheus_text()
    assert '# TYPE t_total counter' in text
    assert 't_total{op="bfs"} 3' in text
    # cumulative buckets: 0.5 ≤ 1.0, 3.0 ≤ 4.0, +Inf carries the total
    assert 't_sec_bucket{le="1"} 1' in text
    assert 't_sec_bucket{le="4"} 2' in text
    assert 't_sec_bucket{le="+Inf"} 2' in text
    assert 't_sec_count 2' in text


def test_noop_registry_reads():
    reg = noop_registry()
    assert reg.enabled is False
    c = reg.counter("anything", labels=("op",))
    c.inc(99, op="bfs")  # discarded
    assert math.isnan(c.value())
    assert c.count() == 0
    assert reg.snapshot() == {}
    assert reg.to_prometheus_text() == ""


def test_use_registry_scopes_the_default():
    outer = get_registry()
    mine = Registry()
    with use_registry(mine):
        assert get_registry() is mine
    assert get_registry() is outer


# ----------------------------------------------------------------------
# Histogram percentile extraction vs numpy
# ----------------------------------------------------------------------
@pytest.mark.parametrize("dist", ["lognormal", "uniform"])
def test_histogram_percentiles_match_numpy(dist):
    rng = np.random.default_rng(11)
    if dist == "lognormal":
        samples = rng.lognormal(mean=-6.0, sigma=1.0, size=4000)
    else:
        samples = rng.uniform(1e-4, 5e-2, size=4000)
    reg = Registry()
    h = reg.histogram("t_sec", buckets=exp_buckets(1e-6, 100.0, per_decade=24))
    for v in samples:
        h.observe(float(v))
    # bucket-walk extraction is exact to one bucket's relative width:
    # 24/decade → ≤ 10% per bucket; allow 2 bucket widths of slack
    ratio = 10.0 ** (1 / 24.0)
    for q in (50.0, 99.0):
        want = float(np.quantile(samples, q / 100.0))
        got = h.percentile(q)
        assert want / ratio**2 <= got <= want * ratio**2, (q, want, got)
    assert h.count() == len(samples)
    assert h.sum() == pytest.approx(samples.sum(), rel=1e-9)


def test_histogram_single_sample_exact():
    reg = Registry()
    h = reg.histogram("t_sec")
    h.observe(0.0123)
    # min/max clamping makes a single-sample series exact at any q
    assert h.percentile(50) == pytest.approx(0.0123)
    assert h.percentile(99) == pytest.approx(0.0123)
    assert math.isnan(reg.histogram("t_empty").percentile(99))


def test_histogram_label_filter_aggregates():
    reg = Registry()
    h = reg.histogram("t_sec", labels=("op",), buckets=(1.0, 10.0))
    for v in (0.5, 0.5, 5.0):
        h.observe(v, op="bfs")
    h.observe(5.0, op="wbfs")
    assert h.count(op="bfs") == 3
    assert h.count() == 4
    with pytest.raises(ValueError):
        h.count(bogus="x")


# ----------------------------------------------------------------------
# Exact PSAM counter mirroring
# ----------------------------------------------------------------------
def test_psam_charges_mirror_exactly():
    g = _graph()
    reg = Registry()
    cost = PSAMCost(registry=reg)
    cost.charge_edgemap_dense(g)
    cost.charge_edgemap_batched(g, 8)
    cost.charge_filter_pack(g, touched_blocks=4)
    cost.charge_small(123)
    reads = reg.counter("sage_psam_large_read_words_total", labels=("charge",))
    small = reg.counter("sage_psam_small_ops_words_total", labels=("charge",))
    writes = reg.counter("sage_psam_large_write_words_total", labels=("charge",))
    # the unlabeled aggregate equals the dataclass fields word for word
    assert reads.value() == cost.large_reads
    assert small.value() == cost.small_ops
    assert writes.value() == cost.large_writes
    # and the per-charge-kind split is disjoint and complete
    kinds = {k for (k,), _ in reads.series()} | {k for (k,), _ in small.series()}
    assert {"edgemap_dense", "edgemap_batched", "filter_pack", "small"} <= kinds
    assert small.value(charge="small") == 123


def test_psam_default_registry_routing():
    g = _graph()
    reg = Registry()
    with use_registry(reg):
        cost = PSAMCost()  # no injected registry → resolves the default
        cost.charge_edgemap_dense(g)
    assert (
        reg.counter("sage_psam_large_read_words_total", labels=("charge",)).value()
        == cost.large_reads
    )


# ----------------------------------------------------------------------
# Engine + service instrumentation
# ----------------------------------------------------------------------
def test_engine_occupancy_nan_when_idle_and_reset_stats():
    g = _graph()
    reg = Registry()
    eng = QueryEngine(g, registry=reg)
    assert math.isnan(eng.occupancy)  # idle engine: no occupancy, not 1.0
    hs = [eng.submit("bfs", src=i) for i in range(3)]
    res = eng.flush()
    assert len(res) == len(hs)
    assert eng.occupancy == pytest.approx(3 / 4)  # 3 real lanes, padded to 4
    assert reg.gauge("sage_engine_occupancy").value() == pytest.approx(3 / 4)
    assert reg.counter("sage_engine_padded_lanes_total").value() == 1
    assert (
        reg.histogram("sage_engine_batch_size", labels=("op",)).count(op="bfs") == 1
    )
    eng.reset_stats()
    assert math.isnan(eng.occupancy)
    assert reg.counter("sage_engine_padded_lanes_total").value() == 0
    # engine-scoped reset leaves other families (PSAM mirror) alone
    assert (
        reg.counter("sage_psam_large_read_words_total", labels=("charge",)).value()
        > 0
    )


def test_engine_cache_counters_are_the_retrace_contract():
    g = _graph()
    reg = Registry()
    eng = QueryEngine(g, registry=reg)
    hits = reg.counter("sage_engine_cache_hits_total", labels=("cache",))
    misses = reg.counter("sage_engine_cache_misses_total", labels=("cache",))
    eng.serve([("bfs", {"src": 0}), ("bfs", {"src": 1})])  # one bucket: B=2
    assert misses.value(cache="engine") == 1
    assert hits.value(cache="engine") == 0
    eng.serve([("bfs", {"src": 2}), ("bfs", {"src": 3})])  # same (op, B) key
    assert misses.value(cache="engine") == 1  # zero steady-state retraces
    assert hits.value(cache="engine") == 1
    assert sum(eng.trace_counts.values()) == 1


def test_service_metrics_populate():
    g = _graph()
    reg = Registry()
    svc = ServingService(
        g,
        config=ServiceConfig(slo=0.01, max_batch=4, budgets={"t1": (1.0, 1.0)}),
        registry=reg,
    )
    assert math.isnan(svc.occupancy)  # idle service: NaN, not 1.0
    svc.submit("bfs", tenant="a", src=0, now=0.0)
    svc.submit("wbfs", tenant="a", src=1, now=0.001)
    svc.submit("bfs", tenant="t1", src=2, now=0.002)  # over budget → rejected
    done = svc.tick(0.02)  # past the deadline
    assert len(done) == 2
    assert reg.counter(
        "sage_service_submitted_total", labels=("op", "tenant")
    ).value() == 3
    adm = reg.counter("sage_service_admission_total", labels=("outcome", "tenant"))
    assert adm.value(outcome="admitted") == 2
    assert adm.value(outcome="rejected", tenant="t1") == 1
    assert reg.counter("sage_service_flushes_total", labels=("cause",)).value(
        cause="deadline"
    ) == 1
    lat = reg.histogram("sage_service_latency_seconds", labels=("op", "tenant"))
    assert lat.count() == 2
    assert lat.count(op="bfs", tenant="a") == 1
    # latency = virtual queue wait + real drain wall: ≥ the virtual wait
    assert lat.percentile(50, op="bfs", tenant="a") >= 0.02 - 0.0
    assert reg.gauge("sage_service_queue_depth").value() == 0
    drift = reg.gauge("sage_psam_drift_words_per_second").value()
    assert drift > 0 and not math.isnan(drift)
    assert 0 < svc.occupancy <= 1
    assert reg.gauge("sage_service_occupancy").value() == pytest.approx(
        svc.occupancy
    )


# ----------------------------------------------------------------------
# Bit-exactness: instrumentation on vs noop, all plan shapes
# ----------------------------------------------------------------------
@pytest.mark.parametrize("strategy", ["dense", "sparse_streamed"])
@pytest.mark.parametrize("B", [1, 8])
def test_results_bit_identical_enabled_vs_noop(strategy, B):
    from repro.algorithms import bfs_batched

    g = _graph()
    plan = make_plan(g, strategy=strategy)
    srcs = list(range(B))

    def run():
        eng = QueryEngine(g, plan=plan)
        return eng.serve([("bfs", {"src": s}) for s in srcs])

    with use_registry(Registry()):
        res_on = run()
    with use_registry(noop_registry()):
        res_off = run()
    direct = bfs_batched(g, np.asarray(srcs, np.int32), plan=plan)
    for i, ((p_on, l_on), (p_off, l_off)) in enumerate(zip(res_on, res_off)):
        assert np.array_equal(np.asarray(p_on), np.asarray(p_off)), (strategy, B, i)
        assert np.array_equal(np.asarray(l_on), np.asarray(l_off)), (strategy, B, i)
        assert np.array_equal(np.asarray(p_on), np.asarray(direct[0][i]))
        assert np.array_equal(np.asarray(l_on), np.asarray(direct[1][i]))


def test_results_bit_identical_sharded_and_pipelined():
    # mesh {2, 4} × pipelined needs fake CPU devices → subprocess, like the
    # rest of the mesh suite
    out = _run(
        """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
from repro.compat import make_mesh, use_mesh
from repro.core import make_plan
from repro.data import rmat_graph
from repro.obs import Registry, noop_registry, use_registry
from repro.serving import QueryEngine

g = rmat_graph(256, 1024, weighted=True, seed=3, block_size=32)
for shape in [(2,), (4,)]:
    for pipe in (False, True):
        mesh = make_mesh(shape, ("data",))
        plan = make_plan(g, mesh=mesh, pipeline_rounds=pipe)
        results = []
        for reg in (Registry(), noop_registry()):
            with use_registry(reg):
                eng = QueryEngine(g, plan=plan)
                results.append(
                    eng.serve([("bfs", {"src": s}) for s in range(8)]
                              + [("wbfs", {"src": 5})])
                )
        on, off = results
        for i, (a, b) in enumerate(zip(on, off)):
            fa, fb = np.asarray(a[0] if isinstance(a, tuple) else a), \
                     np.asarray(b[0] if isinstance(b, tuple) else b)
            assert np.array_equal(fa, fb), (shape, pipe, i)
            if isinstance(a, tuple):
                assert np.array_equal(np.asarray(a[1]), np.asarray(b[1]))
print("OK")
"""
    )
    assert "OK" in out


def test_round_loop_metrics_record_eagerly():
    from repro.algorithms import bfs

    g = _graph(weighted=False)
    reg = Registry()
    with use_registry(reg):
        bfs(g, 0)
    rounds = reg.get("sage_round_loop_rounds")
    assert rounds is not None and rounds.count(path="sequential") >= 1
    # BFS on a connected-ish rmat graph runs a plausible round count
    assert 1 <= rounds.percentile(50) <= 256


def test_dump_cli_smoke():
    out = _run(
        "import sys; from repro.obs.dump import main; "
        "sys.exit(main(['--requests', '6', '--n', '128', '--m', '512']))"
    )
    assert "sage_service_latency_seconds" in out
    assert "sage_psam_large_read_words_total" in out
