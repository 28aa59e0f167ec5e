"""Pure-numpy/scipy reference implementations + validity predicates for the
18 Sage algorithms.  These are the ground truth the JAX engine is tested
against."""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp
import scipy.sparse.csgraph as csg


def to_scipy(g):
    n = g.n
    src = np.asarray(g.edge_src)
    dst = np.asarray(g.edge_dst)
    w = np.asarray(g.edge_w)
    valid = dst < n
    return sp.csr_matrix(
        (w[valid], (src[valid], dst[valid])), shape=(n, n)
    )


def edges_of(g):
    n = g.n
    src = np.asarray(g.edge_src)
    dst = np.asarray(g.edge_dst)
    valid = dst < n
    return src[valid], dst[valid], np.asarray(g.edge_w)[valid]


def adj_sets(g):
    s, d, _ = edges_of(g)
    adj = [set() for _ in range(g.n)]
    for a, b in zip(s, d):
        adj[a].add(int(b))
    return adj


# ---------------- shortest paths ----------------
def bfs_levels(g, src):
    A = to_scipy(g)
    A.data[:] = 1.0
    # hop counts by Dijkstra on unit weights: O(m log n), where
    # Bellman-Ford's O(n m) cannot reach Graph500 scales
    dist = csg.shortest_path(A, method="D", unweighted=True, indices=src)
    lev = np.where(np.isinf(dist), -1, dist).astype(np.int64)
    return lev


def dijkstra_int(g, src):
    A = to_scipy(g)
    dist = csg.dijkstra(A, indices=src)
    return dist


def bellman_ford_ref(g, src):
    A = to_scipy(g)
    return csg.shortest_path(A, method="BF", indices=src)


def widest_path_ref(g, src):
    # max-min path: binary-search-free O(n m) DP
    s, d, w = edges_of(g)
    width = np.full(g.n, -np.inf)
    width[src] = np.inf
    for _ in range(g.n):
        nw = np.minimum(width[s], w)
        best = width.copy()
        np.maximum.at(best, d, nw)
        if np.array_equal(best, width):
            break
        width = best
    return width


def betweenness_ref(g, src):
    adj = adj_sets(g)
    n = g.n
    from collections import deque

    sigma = np.zeros(n)
    dist = np.full(n, -1)
    preds = [[] for _ in range(n)]
    sigma[src] = 1
    dist[src] = 0
    q = deque([src])
    order = []
    while q:
        v = q.popleft()
        order.append(v)
        for u in adj[v]:
            if dist[u] < 0:
                dist[u] = dist[v] + 1
                q.append(u)
            if dist[u] == dist[v] + 1:
                sigma[u] += sigma[v]
                preds[u].append(v)
    delta = np.zeros(n)
    for v in reversed(order):
        for p in preds[v]:
            delta[p] += sigma[p] / sigma[v] * (1 + delta[v])
    delta[src] = 0
    return delta


# ---------------- connectivity ----------------
def components_ref(g):
    A = to_scipy(g)
    _, labels = csg.connected_components(A, directed=False)
    # canonicalize: min vertex id per component
    mins = {}
    for v, l in enumerate(labels):
        mins.setdefault(l, v)
    return np.array([mins[l] for l in labels])


def check_spanning_forest(g, parents, labels):
    n = g.n
    parents = np.asarray(parents)
    labels = np.asarray(labels)
    ref = components_ref(g)
    if not np.array_equal(labels, ref):
        return False, "labels mismatch"
    adj = adj_sets(g)
    n_comp = len(set(ref.tolist()))
    n_edges = int(np.sum(parents != np.arange(n)))
    if n_edges != n - n_comp:
        return False, f"edge count {n_edges} != {n - n_comp}"
    for v in range(n):
        p = parents[v]
        if p == v:
            continue
        if p < 0 or int(p) not in adj[v]:
            return False, f"parent edge ({v},{p}) not in graph"
    # acyclicity: follow parents to root
    for v in range(n):
        seen = set()
        u = v
        while parents[u] != u:
            if u in seen:
                return False, "cycle"
            seen.add(u)
            u = parents[u]
    return True, "ok"


def bicomp_ref(g):
    """Iterative Tarjan; returns dict {frozenset((u,v)): comp_id}."""
    adj = [[] for _ in range(g.n)]
    s, d, _ = edges_of(g)
    for a, b in zip(s, d):
        if a < b:
            adj[a].append(int(b))
            adj[b].append(int(a))
    n = g.n
    visited = [False] * n
    disc = [0] * n
    low = [0] * n
    timer = [1]
    comp_of = {}
    cid = [0]
    for root in range(n):
        if visited[root]:
            continue
        stack = [(root, -1, iter(adj[root]))]
        estack = []
        visited[root] = True
        disc[root] = low[root] = timer[0]
        timer[0] += 1
        while stack:
            v, parent, it = stack[-1]
            advanced = False
            for u in it:
                if not visited[u]:
                    estack.append((v, u))
                    visited[u] = True
                    disc[u] = low[u] = timer[0]
                    timer[0] += 1
                    stack.append((u, v, iter(adj[u])))
                    advanced = True
                    break
                elif u != parent and disc[u] < disc[v]:
                    estack.append((v, u))
                    low[v] = min(low[v], disc[u])
            if not advanced:
                stack.pop()
                if stack:
                    pv = stack[-1][0]
                    low[pv] = min(low[pv], low[v])
                    if low[v] >= disc[pv]:
                        # pop bicomp
                        comp = cid[0]
                        cid[0] += 1
                        while estack:
                            e = estack.pop()
                            comp_of[frozenset(e)] = comp
                            if frozenset(e) == frozenset((pv, v)):
                                break
    return comp_of


def check_bicomp(g, slot_labels):
    """slot_labels int[slots]; same undirected edge → same label; partition
    must match Tarjan's."""
    ref = bicomp_ref(g)
    n = g.n
    src = np.asarray(g.edge_src)
    dst = np.asarray(g.edge_dst)
    lab = np.asarray(slot_labels)
    valid = dst < n
    ours = {}
    for a, b, l in zip(src[valid], dst[valid], lab[valid]):
        k = frozenset((int(a), int(b)))
        if k in ours and ours[k] != l:
            return False, f"direction mismatch on {k}"
        ours[k] = l
    if set(ours.keys()) != set(ref.keys()):
        return False, "edge set mismatch"
    # bijection between label sets
    fwd, bwd = {}, {}
    for k, r in ref.items():
        o = ours[k]
        if r in fwd and fwd[r] != o:
            return False, f"ref comp {r} split"
        if o in bwd and bwd[o] != r:
            return False, f"our comp {o} merged"
        fwd[r] = o
        bwd[o] = r
    return True, "ok"


# ---------------- covering ----------------
def check_mis(g, in_set):
    in_set = np.asarray(in_set)
    s, d, _ = edges_of(g)
    if np.any(in_set[s] & in_set[d]):
        return False, "not independent"
    # maximal: every out vertex has an in neighbor
    covered = np.zeros(g.n, dtype=bool)
    np.logical_or.at(covered, d, in_set[s])
    if np.any(~in_set & ~covered):
        bad = np.flatnonzero(~in_set & ~covered)
        # isolated vertices must be in the set
        return False, f"not maximal at {bad[:5]}"
    return True, "ok"


def check_matching(g, partner):
    partner = np.asarray(partner)
    adj = adj_sets(g)
    for v, p in enumerate(partner):
        if p >= 0:
            if partner[p] != v:
                return False, f"asymmetric at {v}"
            if p not in adj[v]:
                return False, f"non-edge match ({v},{p})"
    matched = partner >= 0
    s, d, _ = edges_of(g)
    exposed = ~matched[s] & ~matched[d]
    if np.any(exposed):
        return False, "not maximal"
    return True, "ok"


def check_coloring(g, color):
    color = np.asarray(color)
    if np.any(color < 0):
        return False, "uncolored vertices"
    s, d, _ = edges_of(g)
    if np.any(color[s] == color[d]):
        return False, "adjacent same color"
    deg = np.asarray(g.degrees)
    if np.any(color > deg):
        return False, "color > degree"
    return True, "ok"


def greedy_set_cover_size(g, sets_mask):
    sets_mask = np.asarray(sets_mask)
    adj = adj_sets(g)
    elems = set(
        v
        for v in range(g.n)
        if not sets_mask[v] and any(sets_mask[u] for u in adj[v])
    )
    uncovered = set(elems)
    size = 0
    while uncovered:
        best, gain = -1, 0
        for v in range(g.n):
            if sets_mask[v]:
                gn = len(adj[v] & uncovered)
                if gn > gain:
                    best, gain = v, gn
        if best < 0:
            break
        uncovered -= adj[best]
        size += 1
    return size


def check_set_cover(g, sets_mask, in_cover):
    sets_mask = np.asarray(sets_mask)
    in_cover = np.asarray(in_cover)
    adj = adj_sets(g)
    if np.any(in_cover & ~sets_mask):
        return False, "non-set in cover"
    for v in range(g.n):
        if sets_mask[v]:
            continue
        nbr_sets = [u for u in adj[v] if sets_mask[u]]
        if nbr_sets and not any(in_cover[u] for u in nbr_sets):
            return False, f"element {v} uncovered"
    return True, "ok"


# ---------------- substructure ----------------
def triangles_ref(g):
    A = to_scipy(g)
    A.data[:] = 1.0
    A = ((A + A.T) > 0).astype(np.float64)
    return int(round((A @ A).multiply(A).sum() / 6.0))


def kcore_ref(g):
    adj = adj_sets(g)
    n = g.n
    deg = np.array([len(a) for a in adj])
    core = np.zeros(n, dtype=np.int64)
    alive = np.ones(n, dtype=bool)
    k = 0
    while alive.any():
        mn = deg[alive].min()
        k = max(k, mn)
        peel = [v for v in range(n) if alive[v] and deg[v] <= k]
        while peel:
            nxt = []
            for v in peel:
                if not alive[v]:
                    continue
                core[v] = k
                alive[v] = False
                for u in adj[v]:
                    if alive[u]:
                        deg[u] -= 1
                        if deg[u] <= k:
                            nxt.append(u)
            peel = nxt
    return core


def densest_ref_lower_bound(g):
    """Best density over sequential Charikar peel (≥ ρ*/2)."""
    adj = adj_sets(g)
    n = g.n
    deg = np.array([len(a) for a in adj], dtype=np.float64)
    alive = np.ones(n, dtype=bool)
    m2 = deg.sum()
    best = 0.0
    for _ in range(n):
        na = alive.sum()
        if na == 0:
            break
        best = max(best, m2 / 2.0 / na)
        v = int(np.argmin(np.where(alive, deg, np.inf)))
        alive[v] = False
        m2 -= 2 * deg[v]
        for u in adj[v]:
            if alive[u]:
                deg[u] -= 1
        deg[v] = 0
    return best


def pagerank_ref(g, damping=0.85, iters=100, eps=1e-6):
    s, d, _ = edges_of(g)
    n = g.n
    deg = np.bincount(s, minlength=n).astype(np.float64)
    pr = np.full(n, 1.0 / n)
    for _ in range(iters):
        contrib = np.where(deg > 0, pr / np.maximum(deg, 1), 0.0)
        agg = np.zeros(n)
        np.add.at(agg, d, contrib[s])
        dangling = pr[deg == 0].sum()
        new = (1 - damping) / n + damping * (agg + dangling / n)
        if np.abs(new - pr).sum() < eps:
            pr = new
            break
        pr = new
    return pr


# ---------------- validity for randomized decompositions ----------------
def check_ldd(g, cluster, beta, slack=6.0):
    cluster = np.asarray(cluster)
    if np.any(cluster < 0):
        return False, "unclustered vertices"
    s, d, _ = edges_of(g)
    inter = cluster[s] != cluster[d]
    m = len(s)
    if m and inter.sum() > max(slack * beta * m, 32):
        return False, f"too many inter-cluster edges: {inter.sum()}/{m}"
    # clusters connected: BFS within cluster from center
    adj = adj_sets(g)
    for c in set(cluster.tolist()):
        members = set(np.flatnonzero(cluster == c).tolist())
        if int(c) not in members:
            return False, f"center {c} not in own cluster"
        seen = {int(c)}
        stack = [int(c)]
        while stack:
            v = stack.pop()
            for u in adj[v]:
                if u in members and u not in seen:
                    seen.add(u)
                    stack.append(u)
        if seen != members:
            return False, f"cluster {c} disconnected"
    return True, "ok"


def check_spanner(g, edge_mask, k, slack=4.0):
    n = g.n
    src = np.asarray(g.edge_src)
    dst = np.asarray(g.edge_dst)
    em = np.asarray(edge_mask)
    valid = dst < n
    Hs, Hd = src[valid & em], dst[valid & em]
    A = to_scipy(g)
    A.data[:] = 1.0
    H = sp.csr_matrix((np.ones(len(Hs)), (Hs, Hd)), shape=(n, n))
    dg = csg.shortest_path(A, unweighted=True)
    dh = csg.shortest_path(H, unweighted=True) if len(Hs) else np.full((n, n), np.inf)
    finite = np.isfinite(dg) & (dg > 0)
    if not np.all(np.isfinite(dh[finite])):
        return False, "spanner disconnects"
    stretch = dh[finite] / dg[finite]
    if stretch.max() > slack * max(k, 1) + 2:
        return False, f"stretch {stretch.max()} too large"
    return True, "ok"
