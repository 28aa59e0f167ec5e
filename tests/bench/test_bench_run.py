"""Whole runs of the harness on the CPU at a tiny scale: the result line,
the refusal of a host without a chip, the data-driven layout, and the
faults that the check has to catch."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness
from benchkit import REPO, make_root, run_cell

CELLS = ["kron21.bfs", "kron21z.pagerank"]


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return env


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_last_line_meets_the_contract(tiny_root, capsys, cell, trace):
    rc, res, err = run_cell(tiny_root, cell, capsys, trace=trace)
    assert rc == 0
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 1
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    if trace:
        want = {m["name"] for m in spec["per_layer"] if cell in m["workloads"]}
        # the CPU has no device plane: only the host-clock metric reads
        assert set(res["metrics"]) == {"ingest.build_s"}
        assert {"busy_s", "window_s"} <= set(res["device"])
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
        assert set(res["metrics"]) <= want
    else:
        assert set(res["metrics"]) == {"evps", "setup_s"}
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(res["device"])
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
    # each number compared, beside its limit, ends standard error
    tail = err.strip().splitlines()[-len(res["checks"]):]
    assert tail == [f"check {k} value={c['value']!r} limit={c['limit']!r}"
                    for k, c in res["checks"].items()]


def test_a_host_without_a_chip_exits_nonzero_and_prints_no_result():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "kron21.bfs", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=_env(), capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout and "evps" not in p.stdout
    assert "no accelerator" in p.stderr


def test_a_checkout_of_only_the_benchmark_exits_nonzero(tmp_path):
    root = tmp_path / "only"
    root.mkdir()
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    for p in json.load(open(os.path.join(REPO, "BENCHMARK.json")))["paths"]:
        shutil.copytree(os.path.join(REPO, p), root / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "kron21.bfs", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=root, env=_env(), capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and "{" not in p.stdout


def test_a_new_config_cell_and_metric_are_files_and_entries(tmp_path, capsys):
    root = make_root(tmp_path, scale=None)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    # a throwaway configuration, cell and per-layer metric, added as new
    # files and new entries only
    cfg = json.loads((root / "bench/configs/g500-kron21.json").read_text())
    cfg["name"] = "tiny-kron9"
    cfg["generator"]["scale"] = 9
    (root / "bench/configs/tiny-kron9.json").write_text(json.dumps(cfg))
    (root / "bench/metrics/jobs.count.py").write_text(
        "def read(record):\n    return float(record['window']['jobs'])\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-kron9", "source": "test", "reduced": [], "why": "test",
                            "file": "bench/configs/tiny-kron9.json"})
    spec["workloads"].append({"name": "tiny9.bfs", "config": "tiny-kron9",
                              "traffic": "graph500-bfs", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "jobs.count", "unit": "jobs", "better": "higher",
                              "source": "host_clock", "layer": "harness", "moves": "evps",
                              "workloads": ["tiny9.bfs"]})
    new_spec = json.dumps(spec)
    (root / "BENCHMARK.json").write_text(new_spec)
    rc, res, _ = run_cell(root, "tiny9.bfs", capsys, trace=1)
    assert rc == 0 and res["correct"] is True
    assert res["metrics"]["jobs.count"]["value"] == res["attempted"]
    for p, data in before.items():
        if p.name != "BENCHMARK.json":
            assert p.read_bytes() == data, f"{p} was edited"
    old = json.loads(before[root / "BENCHMARK.json"])
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert spec[key][:len(old[key])] == old[key]


def test_unknown_device_kind_has_no_peaks():
    cell = harness.Cell(REPO, "kron21z.pagerank")
    assert cell.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        cell.peaks("TPU v9 imaginary")


# --- faults planted under the timed path: each must make correct false ---

def _state_unchanged(monkeypatch):
    import repro.algorithms.eigen as eigen
    import repro.algorithms.traversal as traversal

    def no_rounds(g, state, **_kw):
        return state

    monkeypatch.setattr(traversal, "round_loop", no_rounds)
    monkeypatch.setattr(eigen, "round_loop", no_rounds)


def _answer_altered(monkeypatch):
    import repro.algorithms as algorithms

    bfs, pagerank = algorithms.bfs, algorithms.pagerank

    def bad_bfs(*a, **k):
        parents, levels = bfs(*a, **k)
        return parents, levels.at[0].add(1)

    def bad_pagerank(*a, **k):
        pr, iters = pagerank(*a, **k)
        return pr.at[0].multiply(1.01), iters

    monkeypatch.setattr(algorithms, "bfs", bad_bfs)
    monkeypatch.setattr(algorithms, "pagerank", bad_pagerank)


@pytest.mark.parametrize("fault", [_state_unchanged, _answer_altered],
                         ids=["state_unchanged", "answer_altered"])
@pytest.mark.parametrize("cell", CELLS)
def test_a_broken_timed_path_is_not_correct(tiny_root, capsys, monkeypatch, cell, fault):
    fault(monkeypatch)
    rc, res, _ = run_cell(tiny_root, cell, capsys)
    assert rc == 0
    assert res["correct"] is False
    assert res["failed"] == res["attempted"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
