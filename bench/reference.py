"""Plain references: the graph and the jobs' answers from the raw edges.

Independent of the program: nothing here imports ``repro`` or reads what
the program built.  The adjacency comes straight from the generator's
edge tuples (symmetrized, self-loops and duplicates dropped, as the
Graph500 specification asks), and the answers follow the copies of
``tests/oracles.py``'s BFS (hop counts by Dijkstra on unit weights) and
float64 PageRank.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp
import scipy.sparse.csgraph as csg


class RefGraph:
    """Undirected simple graph as a scipy CSR matrix of ones."""

    def __init__(self, n: int, src: np.ndarray, dst: np.ndarray):
        src = np.asarray(src, np.int64)
        dst = np.asarray(dst, np.int64)
        keep = src != dst
        s = np.concatenate([src[keep], dst[keep]])
        d = np.concatenate([dst[keep], src[keep]])
        key = np.unique(s * n + d)
        self.n = int(n)
        self.src = (key // n).astype(np.int64)
        self.dst = (key % n).astype(np.int64)
        self.key = key  # sorted src * n + dst of every directed slot
        self.m_directed = int(key.shape[0])
        self.deg = np.bincount(self.src, minlength=n).astype(np.int64)
        indptr = np.zeros(n + 1, np.int64)
        np.cumsum(self.deg, out=indptr[1:])
        self.adj = sp.csr_matrix(
            (np.ones(self.m_directed, np.float64), self.dst, indptr), shape=(n, n)
        )

    def has_edges(self, u: np.ndarray, v: np.ndarray) -> np.ndarray:
        """bool per pair: is (u, v) an edge?"""
        k = np.asarray(u, np.int64) * self.n + np.asarray(v, np.int64)
        at = np.minimum(np.searchsorted(self.key, k), self.key.shape[0] - 1)
        return self.key[at] == k


def bfs_levels(ref: RefGraph, src: int) -> np.ndarray:
    """Hop count from ``src`` to every vertex, -1 where unreachable."""
    dist = csg.shortest_path(ref.adj, method="D", unweighted=True, indices=[src])[0]
    return np.where(np.isinf(dist), -1, dist).astype(np.int64)


def pagerank(ref: RefGraph, *, iters: int, damping: float, dtype=np.float64) -> np.ndarray:
    """PageRank for a fixed number of iterations, dangling mass spread
    evenly, ranks held in ``dtype`` (float64 for the reference; a lower
    precision gives the control)."""
    n = ref.n
    deg = ref.deg.astype(np.float64)
    pr = np.full(n, 1.0 / n).astype(dtype)
    dangling = ref.deg == 0
    for _ in range(iters):
        p = pr.astype(np.float64)
        contrib = np.where(dangling, 0.0, p / np.maximum(deg, 1.0)).astype(dtype)
        agg = np.bincount(ref.dst, weights=contrib[ref.src].astype(np.float64), minlength=n)
        mass = p[dangling].sum()
        pr = ((1.0 - damping) / n + damping * (agg + mass / n)).astype(dtype)
    return pr.astype(np.float64)
