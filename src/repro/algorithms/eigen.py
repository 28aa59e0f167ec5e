"""Eigenvector problems (§4.3.5) — PageRank.

One iteration is a single dense edgeMap with the sum monoid; the per-vertex
aggregation is a parallel segment-reduce (the paper's depth improvement over
Ligra's sequential neighbor scan).  O(P_it·m) work, O(P_it·log n) depth.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..core.backend import GraphLike
from ..core.edgemap import edgemap_reduce, edgemap_reduce_batched
from ..core.plan import round_loop


def pagerank(
    g: GraphLike,
    *,
    damping: float = 0.85,
    eps: float = 1e-6,
    max_iters: int = 100,
    plan=None,
):
    """Returns (pr float32[n], iters int32).

    ``plan`` (``repro.core.plan``) picks the execution target — the same
    iteration runs single-device or sharded over a mesh, compressed or raw
    (degrees are read off the unsharded graph; they are O(n) vertex state).
    """
    n = g.n
    if plan is not None:
        g = plan.prepare(g)
    # degree vectors ride in the loop state rather than the callbacks'
    # closures: a pipelined round_loop runs the callbacks inside shard_map,
    # which refuses captured arrays that were placed under a mesh context
    deg = jnp.maximum(g.degrees, 1).astype(jnp.float32)
    dangling = g.degrees == 0
    full_mask = jnp.ones(n, dtype=bool)
    pr0 = jnp.full(n, 1.0 / n, jnp.float32)

    def sweep_inputs(state):
        pr, _, _, deg, dangling = state
        contrib = jnp.where(dangling, 0.0, pr / deg)
        return state, full_mask, contrib

    def epilogue(state, s, _touched):
        pr, it, _, deg, dangling = state
        dangling_mass = jnp.sum(jnp.where(dangling, pr, 0.0))
        new = (1.0 - damping) / n + damping * (s + dangling_mass / n)
        err = jnp.sum(jnp.abs(new - pr))
        return new, it + 1, err, deg, dangling

    def cond(state):
        _, it, err, _, _ = state
        return (err > eps) & (it < max_iters)

    pr, iters, _, _, _ = round_loop(
        g, (pr0, jnp.int32(0), jnp.float32(jnp.inf), deg, dangling),
        sweep_inputs=sweep_inputs, epilogue=epilogue, cond_fn=cond,
        monoid="sum", plan=plan, mode="dense",
    )
    return pr, iters


def pagerank_iteration(g: GraphLike, pr: jnp.ndarray, *, damping: float = 0.85, plan=None):
    """A single PageRank iteration (Table 1 'PageRank Iteration' row)."""
    n = g.n
    if plan is not None:
        g = plan.prepare(g)
    deg = jnp.maximum(g.degrees, 1).astype(jnp.float32)
    dangling = g.degrees == 0
    contrib = jnp.where(dangling, 0.0, pr / deg)
    s, _ = edgemap_reduce(
        g, jnp.ones(n, dtype=bool), contrib, monoid="sum", mode="dense", plan=plan
    )
    dangling_mass = jnp.sum(jnp.where(dangling, pr, 0.0))
    return (1.0 - damping) / n + damping * (s + dangling_mass / n)


def pagerank_iteration_batched(
    g: GraphLike, prs: jnp.ndarray, *, damping: float = 0.85, plan=None
):
    """B PageRank iterations over B score vectors in one dense edge sweep.

    ``prs`` is float32[B, n] (one tentative PageRank vector per query);
    returns float32[B, n].  The batch shares a single dense sum-monoid
    edgeMap — the whole-graph block stream is read once — and each row is
    bit-identical to ``pagerank_iteration`` on that row alone (same plan).
    """
    n = g.n
    if plan is not None:
        g = plan.prepare(g)
    B = prs.shape[0]
    deg = jnp.maximum(g.degrees, 1).astype(jnp.float32)
    dangling = g.degrees == 0
    contrib = jnp.where(dangling[None, :], 0.0, prs / deg[None, :])
    s, _ = edgemap_reduce_batched(
        g,
        jnp.ones((B, n), dtype=bool),
        contrib,
        monoid="sum",
        mode="dense",
        plan=plan,
    )
    dangling_mass = jnp.sum(jnp.where(dangling[None, :], prs, 0.0), axis=1)
    return (1.0 - damping) / n + damping * (s + dangling_mass[:, None] / n)
