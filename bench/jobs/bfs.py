"""Graph500 kernel 2: one breadth-first search per job.

The program's call is ``repro.algorithms.bfs(g, key, plan=plan)``, with the
key a traced argument, so one compiled program serves every key.  Keys
are vertices that keep an edge after ingest, without repeats, as in the
Graph500 specification; but where it times 64 keys drawn per run, the
window here times a fixed pool of ``key_pool`` keys in the order of
``--seed`` (see ``draw``), and ``check_keys`` more keys drawn from
``--seed`` are searched after the window and checked with the rest.  Each
job's levels and parents are checked exactly: the levels against the
reference's hop counts, and every parent as a graph edge one level up (the
specification's validation).
"""
from __future__ import annotations

import numpy as np
import scipy.sparse.csgraph as csg

from bench import reference

# Exact comparisons: any wrong level or parent fails the job.
LIMITS = {"bfs_levels_wrong": 0, "bfs_parents_wrong": 0}


# the traffic file's keys that this kind reads
KEYS = ("key_pool", "key_pool_seed", "check_keys")


def draw(info, traffic: dict, seed: int):
    """(warm job, window's jobs, jobs checked after the window), each job a
    tuple of arguments.

    The warm key and the window's pool of ``key_pool`` keys are drawn from
    ``key_pool_seed``, so that every seed does the same timed work in
    another order: a search's time depends on its key (7.2-8.7 s over 36
    keys on one v5e), and a window holds only a few searches.  The
    ``check_keys`` keys searched after the window are drawn from ``seed``
    among the others, so that each run checks keys of its own."""
    cand = np.flatnonzero(info.has_edge)
    pool = np.random.default_rng(int(traffic["key_pool_seed"])).choice(
        cand, size=min(int(traffic["key_pool"]) + 1, cand.size), replace=False)
    keys = pool[1:]
    rng = np.random.default_rng(int(seed) % (1 << 64))
    order = rng.permutation(keys.size)
    rest = np.setdiff1d(cand, pool)
    extra = rng.choice(rest, size=min(int(traffic["check_keys"]), rest.size), replace=False)
    return ((int(pool[0]),), [(int(k),) for k in keys[order]],
            [(int(k),) for k in extra])


def program(plan, traffic: dict):
    import jax.numpy as jnp

    from repro.algorithms import bfs

    def job(g, key):
        return bfs(g, key, plan=plan)

    return job, lambda key: (jnp.int32(key),)


def keep(out):
    parents, levels = out
    return parents, levels


def rounds(answer) -> int:
    """Rounds of the program's round loop: one per level, and one more
    that finds the last level's frontier has no new neighbour."""
    return int(np.max(answer[1])) + 1


def work(info) -> int:
    """EVPS work: n + m (undirected) per job, whatever the search reaches."""
    return info.n + info.m_undirected


def _errors(ref, key: int, parents: np.ndarray, levels: np.ndarray) -> dict:
    want = reference.bfs_levels(ref, key)
    levels = np.where(np.asarray(levels) < 0, -1, np.asarray(levels))
    parents = np.asarray(parents, np.int64)
    wrong_levels = int(np.count_nonzero(levels != want))
    reached = want >= 0
    v = np.flatnonzero(reached & (np.arange(ref.n) != key))
    p = parents[v]
    ok = (p >= 0) & (p < ref.n)
    ps = np.where(ok, p, 0)
    ok &= want[ps] == want[v] - 1
    ok &= ref.has_edges(ps, v)
    bad = int(np.count_nonzero(~ok))
    bad += int(parents[key] != key)
    bad += int(np.count_nonzero(parents[~reached] != -1))
    return {"bfs_levels_wrong": wrong_levels, "bfs_parents_wrong": bad,
            "edges_reached": int(ref.deg[reached].sum()) // 2}


def check(ref, jobs: list, traffic: dict) -> list[dict]:
    """Per job: {check name: number}, plus ``edges_reached`` for TEPS.
    ``jobs`` holds (args, kept answers on the host)."""
    return [_errors(ref, args[0], *answer) for args, answer in jobs]


def control(ref, args: tuple, traffic: dict):
    """The reference BFS, stopped one level early: it breaks the guarantee
    that every vertex reachable from the key gets its hop count."""
    key = args[0]
    order, pred = csg.breadth_first_order(ref.adj, key, directed=True,
                                          return_predecessors=True)
    levels = reference.bfs_levels(ref, key)
    parents = np.where(pred < 0, -1, pred).astype(np.int64)
    parents[key] = key
    last = levels.max()
    if last > 0:
        cut = levels == last
        levels = np.where(cut, -1, levels)
        parents = np.where(cut, -1, parents)
    return parents, levels
