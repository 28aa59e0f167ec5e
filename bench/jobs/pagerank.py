"""Graphalytics-style PageRank: a fixed number of iterations per job.

The program's call is ``repro.algorithms.pagerank(g, damping, eps=0,
max_iters=iterations, plan=plan)``: every iteration is one dense edgeMap.
Every job is the same job on the deployment's graph (PageRank takes no
parameter but the graph).  Each job's ranks are compared with the float64
reference: the L1 error over the sum of ranks, and the worst vertex's
relative error.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

from bench import reference

# Set from readings on one v5e at the cell's size (PERF.md, "Correctness
# limits"): sound runs read 4.69e-7 (L1) and 1.30e-5 (worst vertex) on
# every seed; the control, ranks held in bfloat16, reads 1.46e-3 and 7.9e-3.
LIMITS = {"pr_l1_rel": 1e-4, "pr_vertex_rel": 1e-3, "pr_iterations_gap": 0}


# the traffic file's keys that this kind reads
KEYS = ("iterations", "damping", "eps")


def draw(info, traffic: dict, seed: int):
    """(warm job, window's jobs, jobs checked after the window): PageRank
    takes no argument, so the window repeats one job and nothing more is
    checked after it."""
    return (), [()], []


def program(plan, traffic: dict):
    from repro.algorithms import pagerank

    iters = int(traffic["iterations"])
    damping = float(traffic["damping"])
    eps = float(traffic["eps"])

    def job(g):
        return pagerank(g, damping=damping, eps=eps, max_iters=iters, plan=plan)

    return job, lambda: ()


def keep(out):
    return out


def rounds(answer) -> int:
    """Rounds of the program's round loop: one per iteration."""
    return int(answer[1])


def work(info) -> int:
    """EVPS work: n + m (undirected) per job."""
    return info.n + info.m_undirected


def _reference(ref, traffic: dict, dtype=np.float64) -> np.ndarray:
    return reference.pagerank(ref, iters=int(traffic["iterations"]),
                              damping=float(traffic["damping"]), dtype=dtype)


def check(ref, jobs: list, traffic: dict) -> list[dict]:
    want = _reference(ref, traffic)
    out = []
    for _args, (pr, iters) in jobs:
        err = np.abs(np.asarray(pr, np.float64) - want)
        out.append({
            "pr_l1_rel": float(err.sum() / want.sum()),
            "pr_vertex_rel": float(np.max(err / want)),
            "pr_iterations_gap": abs(int(iters) - int(traffic["iterations"])),
        })
    return out


def control(ref, args: tuple, traffic: dict):
    """The reference with its ranks held in bfloat16, the precision below
    the program's float32."""
    pr = _reference(ref, traffic, dtype=ml_dtypes.bfloat16)
    return pr, np.int32(traffic["iterations"])
