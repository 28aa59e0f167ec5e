"""Each job kind's answers equal the copied reference on CSR and on the
compressed graph, and its control (the reference, broken as a later change
would be tempted to) fails the same check."""
import json
import os

import jax
import numpy as np
import pytest

from bench import graph, harness, reference
from benchkit import REPO

JOBS = {"bfs": "graph500-bfs.json", "pagerank": "graphalytics-pr10.json"}


def _config(layout, scale=10):
    with open(os.path.join(REPO, "bench", "configs", "g500-kron21.json")) as f:
        cfg = json.load(f)
    cfg["generator"]["scale"] = scale
    cfg["layout"] = layout
    if layout == "compressed":
        cfg["encoding"] = {"target": "uint16_delta", "target_bytes": 2}
    return cfg


def _traffic(kind):
    with open(os.path.join(REPO, "bench", "traffic", JOBS[kind])) as f:
        return json.load(f)


def _job(kind):
    return harness.load_module(os.path.join(REPO, "bench", "jobs", kind + ".py"),
                               f"bench_job_{kind}")


def _all(job, info, traffic, seed):
    warm, window, after = job.draw(info, traffic, seed)
    return [warm] + window + after


def _over(rows, limits):
    return [name for r in rows for name, lim in limits.items() if r[name] > lim]


@pytest.fixture(scope="module", params=["csr", "compressed"])
def built(request):
    from repro.core import make_plan

    g, info = graph.build(_config(request.param), {})
    ref = reference.RefGraph(info.n, info.src, info.dst)
    return g, info, make_plan(g), ref


@pytest.mark.parametrize("kind", sorted(JOBS))
def test_program_answers_equal_the_reference(built, kind):
    g, info, plan, ref = built
    job, traffic = _job(kind), _traffic(kind)
    fn, to_args = job.program(plan, traffic)
    compiled = jax.jit(fn)
    jobs = []
    for args in _all(job, info, traffic, 2**31 + 17):
        jobs.append((args, jax.device_get(job.keep(compiled(g, *to_args(*args))))))
    rows = job.check(ref, jobs, traffic)
    assert len(rows) == len(jobs) >= 2
    assert _over(rows, job.LIMITS) == []
    assert info.m_directed == ref.m_directed


@pytest.mark.parametrize("kind", sorted(JOBS))
def test_control_fails_the_check(built, kind):
    _, info, _, ref = built
    job, traffic = _job(kind), _traffic(kind)
    jobs = [(args, job.control(ref, args, traffic))
            for args in _all(job, info, traffic, 5)]
    rows = job.check(ref, jobs, traffic)
    assert len(_over(rows, job.LIMITS)) >= len(rows)


def _keys(job, info, traffic, seed):
    warm, window, after = job.draw(info, traffic, seed)
    return warm[0], [k for (k,) in window], [k for (k,) in after]


def test_bfs_seeds_order_one_pool_and_check_keys_of_their_own(built):
    _, info, _, _ = built
    job, traffic = _job("bfs"), _traffic("bfs")
    runs = {seed: _keys(job, info, traffic, seed) for seed in range(8)}
    warm = {r[0] for r in runs.values()}
    pools = {tuple(sorted(r[1])) for r in runs.values()}
    assert len(warm) == 1 and len(pools) == 1
    pool = list(pools.pop())
    assert len(pool) == traffic["key_pool"] and len(set(pool + list(warm))) == len(pool) + 1
    assert info.has_edge[pool].all()
    assert len({tuple(r[1]) for r in runs.values()}) > 1
    # the keys checked after the window: distinct, with edges, outside the
    # pool, drawn anew by each seed
    for _, _, after in runs.values():
        assert len(after) == traffic["check_keys"] == len(set(after))
        assert info.has_edge[after].all() and not set(after) & (set(pool) | warm)
    assert len({tuple(r[2]) for r in runs.values()}) == len(runs)
    assert runs[3] == _keys(job, info, traffic, 3)


def test_bfs_rounds_are_levels_plus_one():
    job = _job("bfs")
    assert job.rounds((None, np.array([-1, 0, 1, 3, 2]))) == 4


def test_reference_graph_is_symmetric_simple():
    src = np.array([0, 1, 1, 2, 2, 3])
    dst = np.array([1, 0, 1, 3, 3, 2])
    ref = reference.RefGraph(4, src, dst)
    assert ref.m_directed == 4
    assert ref.has_edges(np.array([0, 1, 2, 3, 0]), np.array([1, 0, 3, 2, 2])).tolist() == [
        True, True, True, True, False]
    np.testing.assert_array_equal(reference.bfs_levels(ref, 0), [0, 1, -1, -1])


@pytest.mark.parametrize("where,key", [("top", "weighted"), ("top", "permute_vertices"),
                                       ("generator", "noise")])
def test_a_configuration_key_that_nothing_reads_is_refused(where, key):
    cfg = _config("csr", scale=8)
    (cfg if where == "top" else cfg["generator"])[key] = True
    with pytest.raises(ValueError, match=key):
        graph.build(cfg, {})


def test_a_traffic_key_that_the_job_does_not_read_is_refused(tiny_root):
    p = tiny_root / "bench" / "traffic" / "graph500-bfs.json"
    traffic = json.loads(p.read_text())
    traffic["in_flight"] = 4
    p.write_text(json.dumps(traffic))
    with pytest.raises(ValueError, match="in_flight"):
        harness.Cell(str(tiny_root), "kron21.bfs")
