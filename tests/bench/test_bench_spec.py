"""``BENCHMARK.json`` keeps the shape the benchmark's contract fixes, and
every name in it finds its file."""
import json
import os
import re

import pytest

from benchkit import REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SPEC = json.load(open(os.path.join(REPO, "BENCHMARK.json")))


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert os.path.isdir(os.path.join(REPO, p)) and not p.startswith("/") and ".." not in p
    assert any(SPEC["command"][1].startswith(p + "/") for p in SPEC["paths"])


def test_run_seconds_fits_a_full_check_of_24_cells():
    rs = SPEC["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs():
    names = [c["name"] for c in SPEC["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    files = set()
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["why"]) <= 200 and "\n" not in c["why"] and "\t" not in c["why"]
        assert NAME.match(c["name"]) and 1 <= len(c["source"]) <= 200
        assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert {"generator", "layout", "block_size", "encoding", "assumed"} <= set(cfg)
        assert any(w["config"] == c["name"] for w in SPEC["workloads"])
    assert len({c["source"] for c in SPEC["configs"]}) == len(names)


def test_workloads():
    names = [w["name"] for w in SPEC["workloads"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    pairs = {(w["config"], w["traffic"]) for w in SPEC["workloads"]}
    assert len(pairs) == len(names)
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        traffic = os.path.join(REPO, "bench", "traffic", w["traffic"] + ".json")
        with open(traffic) as f:
            job = json.load(f)["job"]
        assert os.path.isfile(os.path.join(REPO, "bench", "jobs", job + ".py"))
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(1, len(names) // 2)


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_metrics_have_readers_and_the_contract_keys(kind):
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC[kind]:
        keys = {"name", "unit", "better", "source"} | (
            {"bound"} if kind == "end_to_end" else {"layer", "moves"})
        assert keys <= set(m) <= keys | {"workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
        assert os.path.isfile(os.path.join(REPO, "bench", "metrics", m["name"] + ".py"))
        if kind == "end_to_end":
            assert m["source"] in ("host_clock", "device_trace")
            assert 0.01 <= m["bound"] <= 0.25
        else:
            assert m["source"] in ("device_trace", "program_span", "program_counter",
                                   "host_clock")
            assert m["moves"] in e2e and 1 <= len(m["layer"]) <= 200
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    assert "setup_s" in {m["name"] for m in SPEC["end_to_end"]}
    for w in SPEC["workloads"]:
        e2e = [m for m in SPEC["end_to_end"] if w["name"] in m.get("workloads", [w["name"]])]
        layer = [m for m in SPEC["per_layer"] if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2 and layer


def test_size():
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
