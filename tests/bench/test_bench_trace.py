"""The trace reducer: device busy time, scope time and idle gaps."""
import os

import pytest

from bench import tracing
from benchkit import REPO

TESTDATA = os.path.join(REPO, "bench", "testdata")


def _host(window, *spans):
    return [("bench.window", *window)] + [(n, s, e) for n, s, e in spans]


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    us = 1000  # ns
    host = _host((100 * us, 1100 * us), ("bench.job", 100 * us, 1100 * us),
                 ("bench.wait", 150 * us, 1000 * us))
    ops = {"/device:TPU:0": [
        ("%a = s32[8] scatter(%x)", 50 * us, 300 * us),
        ("b", 250 * us, 400 * us),
        ("%c = f32[8] fusion(%x)", 600 * us, 700 * us),
        ("d", 1050 * us, 1300 * us),
        ("e", 1099 * us + 500, 1100 * us),
    ]}
    hlo = {"a": "jit(job)/while/body/sage.round/scatter", "b": "jit(job)/while/body/add",
           "c": "jit(job)/sage.round/y", "d": "jit(job)/sage.round/x"}
    r = tracing.reduce(host, ops, scopes=("sage.round",), hlo_map=hlo)
    assert r["window_s"] == pytest.approx(1000e-6)
    # [100,400] + [600,700] + [1050,1100]
    assert r["busy_s"] == pytest.approx(450e-6)
    # a: 200, c: 100, d: 50 inside the window
    assert r["scope_s"]["sage.round"] == pytest.approx(350e-6)
    assert [[g[0], round(g[1] * 1e6)] for g in r["idle_gaps"]] == [
        ["bench.wait", 350], ["bench.wait", 200]]
    assert r["device_ops"][0] == ["a jit(job)/while/body/sage.round/scatter",
                                  pytest.approx(200e-6)]
    # e lies inside d: d's own time excludes it
    assert dict(r["device_ops"])["d jit(job)/sage.round/x"] == pytest.approx(49.5e-6)
    assert dict(r["device_ops"])["e"] == pytest.approx(0.5e-6)


def test_chips_are_averaged():
    host = _host((0, 100))
    ops = {"/device:TPU:0": [("a", 0, 100)], "/device:TPU:1": [("a", 0, 50)]}
    r = tracing.reduce(host, ops)
    assert r["busy_s"] == pytest.approx(75e-9)
    assert r["chips"] == 2


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        tracing.reduce([("other", 0, 1)], {})


def test_hlo_op_names():
    text = ('  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f, '
            'metadata={op_name="jit(job)/while/body/sage.round/mul" source_file="x.py"}\n'
            '  ROOT %tuple.1 = (f32[8]) tuple(%fusion.3)\n')
    assert tracing.hlo_op_names(text) == {"fusion.3": "jit(job)/while/body/sage.round/mul"}


def test_recorded_v5e_trace():
    """A PageRank run at Kronecker scale 12 on one v5e, traced over a
    0.3 s window of seven jobs: every op of the round loop's body lies
    under ``sage.round``, the device idles only between jobs."""
    host, devices = tracing.load(os.path.join(TESTDATA, "pagerank_scale12_v5e.xplane.pb"))
    with open(os.path.join(TESTDATA, "pagerank_scale12_v5e.hlo.txt")) as f:
        hlo = tracing.hlo_op_names(f.read())
    assert list(devices) == ["/device:TPU:0"]
    r = tracing.reduce(host, devices, scopes=("sage.round",), hlo_map=hlo)
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(0.300173431, abs=1e-9)
    assert r["busy_s"] == pytest.approx(0.290517339, abs=1e-9)
    assert r["scope_s"]["sage.round"] == pytest.approx(0.289916093, abs=1e-9)
    # the segment-sum of the dense round is the op that takes most time
    assert r["device_ops"][0][0] == "fusion.28 jit(job)/while/body/sage.round/scatter-add"
    assert {g[0] for g in r["idle_gaps"]} <= {"bench.wait", "bench.dispatch"}
    assert all(g[1] >= tracing.MIN_GAP_NS / 1e9 for g in r["idle_gaps"])


def test_short_names_of_tpu_events():
    assert tracing.short_name("%fusion.29 = f32[8]{0} fusion(%a), kind=kLoop") == "fusion.29"
    assert tracing.short_name("while.10") == "while.10"


def test_self_time_excludes_nested_ops():
    # a while [0,100] holding a cond [10,60] holding an op [20,30]
    t = tracing._self_times([(0, 100), (10, 60), (20, 30), (70, 80)])
    assert t == {0: 40, 1: 40, 2: 10, 3: 10}


def test_sweep_round_ms_is_scope_time_over_the_window_rounds():
    from bench import harness

    reader = harness.load_module(os.path.join(REPO, "bench", "metrics", "sweep.round_ms.py"),
                                 "bench_metric_sweep_round_ms")
    record = {"trace": {"scope_s": {"sage.round": 2.0}}, "window": {"rounds": 8}}
    assert reader.read(record) == pytest.approx(250.0)
    assert reader.read({"trace": None, "window": {"rounds": 8}}) is None
    assert reader.read({"trace": {"scope_s": {"sage.round": 0.0}},
                        "window": {"rounds": 8}}) is None
