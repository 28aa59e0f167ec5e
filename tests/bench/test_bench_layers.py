"""The readers of the sweep's dense and sparse parts, the decode, and the
ingest's phases, on synthetic records and on a trace recorded on a v5e."""
import os

import pytest

from bench import harness, tracing
from benchkit import REPO

TESTDATA = os.path.join(REPO, "bench", "testdata")
PEAKS = {"hbm_bytes_per_s": 819e9}


def _reader(name):
    return harness.load_module(os.path.join(REPO, "bench", "metrics", name + ".py"),
                               "bench_metric_" + name.replace(".", "_"))


def _traced(scope, seconds, jobs=4, rounds=8):
    return {"trace": {"scope_s": {scope: seconds}}, "window": {"jobs": jobs, "rounds": rounds},
            "peaks": PEAKS}


def _ingest(phases, peaks=PEAKS):
    """A record of a run whose process ingested with ``phases`` seconds
    recorded (None: no ingest gauge at all)."""
    from repro.obs import Registry

    reg = Registry()
    if phases is not None:
        gauge = reg.gauge("sage_ingest_last_seconds", labels=("phase",))
        for phase, s in phases.items():
            gauge.set(s, phase=phase)
    return reg, {"trace": None, "window": {"jobs": 1, "rounds": 1}, "peaks": peaks}


SWEEP = [
    # (metric, scope, record, value)
    ("sweep.dense_s", "sage.sweep.dense", _traced("sage.sweep.dense", 2.0), 0.5),
    ("sweep.sparse_s", "sage.sweep.sparse", _traced("sage.sweep.sparse", 1.0), 0.25),
    ("decode.round_ms", "sage.decode", _traced("sage.decode", 0.04), 5.0),
    # no trace, or the scope found no op (a program without the scope)
    ("sweep.dense_s", "sage.sweep.dense", {"trace": None, "window": {"jobs": 4}}, None),
    ("sweep.sparse_s", "sage.sweep.sparse", _traced("sage.sweep.sparse", 0.0), None),
    ("decode.round_ms", "sage.decode", _traced("sage.decode", 0.0), None),
    ("decode.round_ms", "sage.decode", _traced("sage.decode", 0.04, rounds=0), None),
]
ALL = {"dedupe": 3.0, "layout": 2.0, "fetch": 1.0, "encode": 4.0}
INGEST = [
    # (metric, phases recorded, peaks, value)
    ("ingest.dedupe_s", ALL, PEAKS, 3.0),
    ("ingest.layout_s", ALL, PEAKS, 2.0),
    ("ingest.encode_s", ALL, PEAKS, 4.0),
    # a CSR ingest records no encode; a program without the spans, nothing
    ("ingest.encode_s", {"dedupe": 3.0, "layout": 2.0}, PEAKS, None),
    ("ingest.dedupe_s", None, PEAKS, None),
    # a run on the CPU carries no peaks and reports no phase
    ("ingest.layout_s", ALL, None, None),
]


@pytest.mark.parametrize(
    "metric,scope,record,phases,peaks,value",
    [(m, s, r, None, None, v) for m, s, r, v in SWEEP]
    + [(m, None, None, ph, pk, v) for m, ph, pk, v in INGEST],
    ids=[f"{m}-{i}" for i, (m, *_rest) in enumerate(SWEEP + INGEST)])
def test_reader(metric, scope, record, phases, peaks, value):
    from repro.obs import use_registry

    reader = _reader(metric)
    if scope is not None:
        assert reader.SCOPES == (scope,)
        got = reader.read(record)
    else:
        reg, record = _ingest(phases, peaks)
        with use_registry(reg):
            got = reader.read(record)
    assert got == (None if value is None else pytest.approx(value))


def test_recorded_v5e_bfs_trace_on_a_compressed_graph():
    """BFS on a compressed Kronecker scale-12 graph on one v5e, traced over
    a window of jobs: the round's time splits into a dense sweep, a sparse
    sweep and a decode, and the two sweeps lie inside the rounds."""
    host, devices = tracing.load(os.path.join(TESTDATA, "bfs_z_scale12_v5e.xplane.pb"))
    with open(os.path.join(TESTDATA, "bfs_z_scale12_v5e.hlo.txt")) as f:
        hlo = tracing.hlo_op_names(f.read())
    scopes = ("sage.round", "sage.sweep.dense", "sage.sweep.sparse", "sage.decode")
    r = tracing.reduce(host, devices, scopes=scopes, hlo_map=hlo)
    s = r["scope_s"]
    assert r["chips"] == 1
    assert s["sage.sweep.dense"] > 0 and s["sage.sweep.sparse"] > 0 and s["sage.decode"] > 0
    assert s["sage.sweep.dense"] + s["sage.sweep.sparse"] <= s["sage.round"]
    assert s["sage.round"] <= r["busy_s"]
