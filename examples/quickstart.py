"""Quickstart: the Sage PSAM engine in five minutes.

Builds an RMAT graph (the immutable large-memory structure), makes an
ExecutionPlan (the planner API every benchmark measures), runs a handful of
the 18 algorithms through it, shows the graphFilter in action, and serves a
batch of concurrent queries through the QueryEngine.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.algorithms import bfs, connectivity, kcore, pagerank, triangle_count
from repro.core import PSAMCost, filter_edges_pred, make_filter, make_plan
from repro.data import rmat_graph
from repro.runtime import enable_compile_cache
from repro.serving import QueryEngine


def main():
    enable_compile_cache()
    key = jax.random.PRNGKey(0)
    g = rmat_graph(n=2048, m=16384, weighted=True, seed=42, block_size=64)
    print(f"graph: n={g.n} m={g.m} blocks={g.num_blocks} (F_B={g.block_size})")

    # one plan, every algorithm: the same calls run sharded over a mesh by
    # passing mesh=... here — algorithm code never picks an engine
    plan = make_plan(g)
    print(f"plan: {plan.describe()}")

    parents, levels = bfs(g, 0, plan=plan)
    reached = int(jnp.sum(levels >= 0))
    print(f"BFS from 0: reached {reached} vertices, max level {int(jnp.max(levels))}")

    labels = connectivity(g, key, plan=plan)
    n_comp = len(set(labels.tolist()))
    print(f"connectivity: {n_comp} components")

    pr, iters = pagerank(g, plan=plan)
    top = jnp.argsort(-pr)[:5]
    print(f"pagerank converged in {int(iters)} iters; top-5 vertices: {top.tolist()}")

    core = kcore(g, plan=plan)
    print(f"k-core: max coreness {int(jnp.max(core))}")

    print(f"triangles: {triangle_count(g)}")

    # graphFilter: delete light edges WITHOUT touching the CSR (PSAM rule)
    f = make_filter(g)
    f2, remaining = filter_edges_pred(g, f, lambda s, d, w: w >= 2.0)
    print(
        f"filter: kept {int(remaining)}/{g.m} edges (w>=2) — "
        f"bits={f2.bits.size * 4} bytes of small memory, zero large-memory writes"
    )

    # serving: coalesce concurrent requests into one edge sweep per round
    eng = QueryEngine(g, plan=plan, max_batch=8)
    handles = [eng.submit("bfs", src=s) for s in [0, 17, 99, 512]]
    eng.submit("ppr", src=0, max_rounds=50)
    results = eng.flush()
    print(
        f"served {eng.stats['served']} queries in {eng.stats['batches']} "
        f"batches; BFS(17) reached "
        f"{int(jnp.sum(results[handles[1]][1] >= 0))} vertices"
    )

    cost = PSAMCost()
    cost.charge_edgemap_batched(g, 4)  # one batched sweep, 4 queries
    cost.charge_filter_pack(g, g.num_blocks)
    print(
        f"PSAM accounting for one batched round: work={cost.work:.0f} "
        f"(GBBS-equivalent with in-place packing at omega=4: "
        f"{cost.gbbs_equivalent_work(g.m):.0f})"
    )


if __name__ == "__main__":
    main()
