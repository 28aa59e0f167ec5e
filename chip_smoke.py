#!/usr/bin/env python3
"""Bring-up smoke of the graph engine on a TPU, through its user entry points.

    python chip_smoke.py              # one chip, Graph500 Kronecker scale 21
    python chip_smoke.py --chips 4    # only the four-chip sharded phase

One chip runs three phases on a Kronecker graph with the Graph500
parameters (A=.57, B=C=.19, edgefactor 16, symmetrized, integer weights in
[1, log2 n) for wBFS), generated from ``--seed``:

1. analytics — BFS from ``--keys`` search keys, PageRank for a fixed
   iteration count and connectivity, on the ``CSRGraph`` and then on its
   ``CompressedCSR``, each checked against ``tests/oracles.py``;
2. serving — a dozen BFS / wBFS / PPR requests through
   ``ServingService.submit`` / ``tick``, drained at the widest power-of-two
   batch whose compiled cohort fits the chip, with one BFS lane checked bit
   for bit against its single-query run and one wBFS lane against the
   Dijkstra oracle;
3. kernel — a scale-16 graph, under the exception cap, served by
   ``QueryEngine`` on a ``sparse_streamed`` plan, so that the streamed
   delta-decode Pallas kernel runs natively; results must equal the plain
   ``sparse`` plan's bit for bit and the BFS oracle.

``--chips 4`` builds the same graph and runs only BFS (one key), PageRank,
connectivity and ``QueryEngine`` batched BFS on a four-chip mesh, each
compared with the single-chip run under the contracts ``tests/test_plan.py``
locks (bit-identical; PageRank within the scale-free bounds below).

Each phase prints its sizes, device bytes, the plan that ran and compile and
steady wall seconds.  The last line is ``{"ok": true, "device": ...}`` and
is printed only when every phase passed; any failure exits non-zero.  On a
host whose JAX finds no TPU the script exits non-zero before doing anything.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
# Sized so one chip finishes inside 20 minutes: a dense edgeMap round over
# the 393 M padded slots of scale 22 took ~5 s on a v5e (an unsorted
# scatter over every slot), so every round below is paid in seconds.
DEFAULT_SCALE = 21
PR_ITERS = 10
PPR_ROUNDS = 10
KERNEL_SCALE = 16
SERVE_REQUESTS = 12
SERVE_WIDTHS = (8, 4, 2, 1)
# PageRank agreement, scale-free: every rank is at least (1-d)/n > 0, so the
# per-vertex relative error is defined everywhere.  f32 summation order
# gives ~4e-7 (L1) and ~3e-6 (per vertex) against the float64 oracle at
# scales 12-15; a wrong dangling mass or a lost shard is off by whole percent.
PR_L1_REL = 1e-5
PR_VERTEX_REL = 1e-3


class SmokeError(AssertionError):
    """A result that disagrees with its reference."""


def check(ok, what: str):
    if not ok:
        raise SmokeError(what)


def say(phase: str, **kv):
    parts = " ".join(f"{k}={v}" for k, v in kv.items())
    print(f"[{phase}] {parts}", flush=True)


def block(x):
    import jax

    return jax.block_until_ready(x)


def compile_and_run(fn, *args):
    """(executable, result, compile seconds, wall seconds) of ``jax.jit(fn)``:
    compiled ahead of time, so the one timed call runs no tracing."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    t_compile = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = block(compiled(*args))
    return compiled, out, t_compile, time.perf_counter() - t0


def device_bytes(tree) -> int:
    import jax

    return int(sum(a.nbytes for a in jax.tree.leaves(tree) if hasattr(a, "nbytes")))


def peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def free_bytes() -> int:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("bytes_limit", 0)) - int(stats.get("bytes_in_use", 0))


def pagerank_error(pr, ref) -> tuple[float, float]:
    """(sum|pr-ref| / sum ref, max over vertices of |pr-ref| / ref)."""
    import numpy as np

    pr, ref = np.asarray(pr, np.float64), np.asarray(ref, np.float64)
    err = np.abs(pr - ref)
    return float(err.sum() / ref.sum()), float(np.max(err / ref))


def check_pagerank(pr, ref, what: str) -> tuple[float, float]:
    l1, vertex = pagerank_error(pr, ref)
    check(l1 <= PR_L1_REL, f"{what}: L1 relative error {l1:.3e} > {PR_L1_REL}")
    check(vertex <= PR_VERTEX_REL,
          f"{what}: per-vertex relative error {vertex:.3e} > {PR_VERTEX_REL}")
    return l1, vertex


def host_view(g):
    """Host copies of the flat edge arrays — what the oracles read."""
    import types

    import numpy as np

    return types.SimpleNamespace(
        n=g.n,
        edge_src=np.asarray(g.edge_src),
        edge_dst=np.asarray(g.edge_dst),
        edge_w=np.asarray(g.edge_w),
    )


def build_graph(scale: int, seed: int):
    from repro.data import rmat_graph

    n = 1 << scale
    t0 = time.perf_counter()
    g = block(rmat_graph(n, 16 * n, weighted=True, seed=seed))
    say(
        "graph", kind="kronecker", scale=scale, n=g.n, m=g.m,
        blocks=g.num_blocks, padded_slots=g.num_blocks * g.block_size,
        build_s=round(time.perf_counter() - t0, 3),
    )
    return g


def search_keys(g, k: int, seed: int):
    import numpy as np

    deg = np.asarray(g.degrees)
    rng = np.random.default_rng(seed + 7)
    return [int(v) for v in rng.choice(np.flatnonzero(deg > 0), size=k, replace=False)]


# ----------------------------------------------------------------------
# phase 1: analytics against the oracles
# ----------------------------------------------------------------------
def analytics(name, g, keys, refs, seed):
    """Run BFS, PageRank and connectivity on ``g``, then check them against
    ``refs`` (a future: the oracles compute on the host meanwhile)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.algorithms import bfs, connectivity, pagerank
    from repro.core import make_plan

    plan = make_plan(g)
    say(f"analytics:{name}", plan=plan.describe(), device_bytes=device_bytes(g))

    compiled, out, t_c, t_w = compile_and_run(
        lambda gg, s: bfs(gg, s, plan=plan), g, jnp.int32(keys[0])
    )
    bfs_runs = [(np.asarray(out[1]), t_c, t_w)]
    for key in keys[1:]:
        t0 = time.perf_counter()
        out = block(compiled(g, jnp.int32(key)))
        bfs_runs.append((np.asarray(out[1]), 0.0, time.perf_counter() - t0))
    _, (pr, iters), pr_c, pr_w = compile_and_run(
        lambda gg: pagerank(gg, max_iters=PR_ITERS, eps=0.0, plan=plan), g
    )
    _, labels, cc_c, cc_w = compile_and_run(
        lambda gg, kk: connectivity(gg, kk, plan=plan), g, jax.random.PRNGKey(seed)
    )

    ref = refs.result()
    for i, (key, (lv, t_c, t_w)) in enumerate(zip(keys, bfs_runs)):
        check(
            np.array_equal(np.where(lv < 0, -1, lv), ref["levels"][i]),
            f"{name} BFS levels from {key} differ from the oracle",
        )
        say(
            f"analytics:{name}:bfs", key=key, reached=int((lv >= 0).sum()),
            max_level=int(lv.max()), compile_s=round(t_c, 3), wall_s=round(t_w, 4),
            teps=round(ref["edges_reached"][i] / t_w, 1),
        )

    pr = np.asarray(pr)
    check(int(iters) == PR_ITERS, f"{name} PageRank ran {int(iters)} iterations")
    l1, vertex = check_pagerank(pr, ref["pagerank"], f"{name} PageRank vs oracle")
    say(
        f"analytics:{name}:pagerank", iters=PR_ITERS, l1_rel_err=f"{l1:.3e}",
        max_vertex_rel_err=f"{vertex:.3e}",
        compile_s=round(pr_c, 3), wall_s=round(pr_w, 4),
    )

    check(
        np.array_equal(np.asarray(labels), ref["components"]),
        f"{name} connectivity labels differ from the oracle",
    )
    say(
        f"analytics:{name}:connectivity",
        components=int(np.unique(ref["components"]).size),
        compile_s=round(cc_c, 3), wall_s=round(cc_w, 4),
        peak_bytes_in_use=peak_bytes(),
    )


def oracle_refs(host, keys):
    import numpy as np

    sys.path.insert(0, os.path.join(ROOT, "tests"))
    import oracles

    t0 = time.perf_counter()
    levels = oracles.bfs_levels(host, keys)
    valid = host.edge_dst < host.n
    deg = np.bincount(host.edge_src[valid], minlength=host.n)
    reached_edges = [int(deg[lv >= 0].sum()) // 2 for lv in levels]
    ref = {
        "levels": levels,
        "edges_reached": reached_edges,
        "pagerank": oracles.pagerank_ref(host, iters=PR_ITERS, eps=0.0),
        "components": oracles.components_ref(host),
    }
    say("oracles", seconds=round(time.perf_counter() - t0, 3))
    return ref


# ----------------------------------------------------------------------
# phase 2: serving
# ----------------------------------------------------------------------
def serve_width(c, plan) -> int:
    """Widest power-of-two cohort width whose compiled program fits the
    memory left on the chip (compiled for real, so the probe is exact)."""
    import jax

    from repro.algorithms.traversal import (
        traversal_cohort_init,
        traversal_cohort_rounds,
    )

    for B in SERVE_WIDTHS:
        ops = ["wbfs" if i % 3 == 2 else "bfs" for i in range(B)]
        state, weighted = traversal_cohort_init(c, ops, [0] * B)
        spec = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
        try:
            compiled = jax.jit(
                lambda g, s, w=weighted: traversal_cohort_rounds(
                    g, s, w, quantum=4, plan=plan
                )
            ).lower(c, spec).compile()
        except Exception as e:  # noqa: BLE001 — the compiler's out-of-memory
            if "RESOURCE_EXHAUSTED" not in str(e) and "memory" not in str(e):
                raise
            say("serving:width", B=B, fits=False, reason="compiler: out of HBM")
            continue
        ma = compiled.memory_analysis()
        need = int(ma.temp_size_in_bytes + ma.output_size_in_bytes)
        fits = need <= free_bytes()
        say("serving:width", B=B, temp_and_out_bytes=need, free_bytes=free_bytes(),
            fits=fits)
        if fits:
            return B
    raise SmokeError("no serving width fits on the chip")


def serve_requests(g, seed):
    """(op, source) of every request: 8 BFS, 2 wBFS, 2 PPR."""
    srcs = search_keys(g, SERVE_REQUESTS, seed + 1)
    ops = ["ppr" if i % 6 == 5 else ("wbfs" if i % 6 == 2 else "bfs")
           for i in range(SERVE_REQUESTS)]
    return list(zip(ops, srcs))


def wbfs_ref(host, requests):
    """Dijkstra distances from the first served wBFS source (host oracle)."""
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    import oracles

    src = next(s for op, s in requests if op == "wbfs")
    return src, oracles.dijkstra_int(host, [src])[0]


def serving(c, requests, wref):
    """Drain ``requests`` through the service; ``wref`` is a future of
    :func:`wbfs_ref`."""
    import jax
    import numpy as np

    from repro.algorithms import bfs
    from repro.core import make_plan
    from repro.serving import ServiceConfig, ServingService

    plan = make_plan(c)
    B = serve_width(c, plan)
    svc = ServingService(c, plan=plan, config=ServiceConfig(slo=0.01, max_batch=B))
    ops, srcs = zip(*requests)
    say("serving", plan=plan.describe(), requests=SERVE_REQUESTS, max_batch=B,
        device_bytes=device_bytes(c))
    tickets, done = [], []
    t0 = time.perf_counter()
    for i, (op, s) in enumerate(zip(ops, srcs)):
        params = {"src": s}
        if op == "ppr":
            params.update(eps=1e-4, max_rounds=PPR_ROUNDS)
        tickets.append(svc.submit(op, now=i * 1e-3, **params))
        done += svc.tick(i * 1e-3)
    done += svc.drain(SERVE_REQUESTS * 1e-3 + 1.0)
    wall = time.perf_counter() - t0
    check(len(done) == SERVE_REQUESTS, f"served {len(done)} of {SERVE_REQUESTS}")
    check(all(t.status == "done" for t in tickets), "a request did not complete")
    say("serving:drain", served=len(done), flushes=svc.stats["flushes"],
        cohort_rounds=svc.stats["cohort_rounds"], occupancy=round(svc.occupancy, 3),
        wall_s_incl_compile=round(wall, 3), peak_bytes_in_use=peak_bytes())

    t_bfs = next(t for t in tickets if t.op == "bfs")
    p, lv = block(jax.jit(lambda g, s: bfs(g, s, plan=plan))(c, t_bfs.params["src"]))
    check(
        np.array_equal(np.asarray(t_bfs.result[0]), np.asarray(p))
        and np.array_equal(np.asarray(t_bfs.result[1]), np.asarray(lv)),
        "served BFS lane differs from its single-query run",
    )
    t_w = next(t for t in tickets if t.op == "wbfs")
    src, want = wref.result()
    check(t_w.params["src"] == src, "wBFS oracle ran from another source")
    d = np.asarray(t_w.result)
    check(
        np.array_equal(np.where(np.isinf(want), -1, want), np.where(d == np.iinfo(np.int32).max, -1, d)),
        "served wBFS lane differs from the Dijkstra oracle",
    )
    t_p = next(t for t in tickets if t.op == "ppr")
    check(
        bool(np.all(np.isfinite(np.asarray(t_p.result[0])))), "PPR lane not finite"
    )
    say("serving:lanes", bfs_bit_exact=True, wbfs_oracle=True, ppr_finite=True)


# ----------------------------------------------------------------------
# phase 3: the streamed-decode kernel on its path
# ----------------------------------------------------------------------
def kernel_phase(seed):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.algorithms.traversal import bfs_batched
    from repro.core import compress, make_plan
    from repro.core.compressed import exception_dense
    from repro.serving import QueryEngine

    sys.path.insert(0, os.path.join(ROOT, "tests"))
    import oracles

    g = build_graph(KERNEL_SCALE, seed)
    c = compress(g)
    check(not exception_dense(c), "the kernel graph must be under the exception cap")
    streamed = make_plan(c, strategy="sparse_streamed")
    plain = make_plan(c, strategy="sparse")
    d = streamed.decisions
    say("kernel", scale=KERNEL_SCALE, exceptions=c.n_exceptions,
        plan=streamed.describe(), device_bytes=device_bytes(c))
    check(d.streamed == "kernel", f"streamed rounds fall back: {d.streamed}")
    check(d.lowering == "native", f"kernel lowering is {d.lowering}")

    B = 8
    srcs = jnp.asarray(search_keys(g, B, seed), jnp.int32)
    lowered = jax.jit(lambda cc, s: bfs_batched(cc, s, plan=streamed)).lower(c, srcs)
    check(
        "tpu_custom_call" in lowered.as_text(),
        "no Pallas kernel in the streamed program",
    )

    results = {}
    for label, plan in (("sparse_streamed", streamed), ("sparse", plain)):
        eng = QueryEngine(c, plan=plan, max_batch=B)
        t0 = time.perf_counter()
        handles = [eng.submit("bfs", src=int(s)) for s in srcs]
        first = eng.flush()
        t_first = time.perf_counter() - t0
        t0 = time.perf_counter()
        handles = [eng.submit("bfs", src=int(s)) for s in srcs]
        out = eng.flush()
        t_w = time.perf_counter() - t0
        results[label] = [tuple(np.asarray(a) for a in out[h]) for h in handles]
        del first
        say(f"kernel:{label}", batch=B, lowering=plan.decisions.lowering,
            streamed=plan.decisions.streamed, first_flush_s=round(t_first, 3),
            wall_s=round(t_w, 4))
    for a, b in zip(results["sparse_streamed"], results["sparse"]):
        check(all(np.array_equal(x, y) for x, y in zip(a, b)),
              "sparse_streamed differs from sparse")
    ref = oracles.bfs_levels(host_view(g), [int(s) for s in srcs])
    for (_, lv), want in zip(results["sparse_streamed"], ref):
        check(np.array_equal(np.where(lv < 0, -1, lv), want),
              "streamed BFS levels differ from the oracle")
    say("kernel:check", kernel_in_hlo=True, bit_identical_to_sparse=True,
        oracle=True, peak_bytes_in_use=peak_bytes())


# ----------------------------------------------------------------------
# --chips 4: the sharded plan against the single-chip run
# ----------------------------------------------------------------------
def four_chips(scale, seed, nkeys):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.algorithms import bfs, connectivity, pagerank
    from repro.compat import make_mesh, use_mesh
    from repro.core import compress, make_plan
    from repro.serving import QueryEngine

    check(len(jax.devices()) >= 4, f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    g = build_graph(scale, seed)
    keys = search_keys(g, nkeys, seed)
    c = compress(g)
    del g
    mesh = make_mesh((4,), ("data",))
    single = make_plan(c)
    sharded = make_plan(c, mesh=mesh)
    gs = sharded.prepare(c)
    sets = sorted({len(a.sharding.device_set) for a in jax.tree.leaves(gs)})
    say("mesh", plan=sharded.describe(), leaf_device_set_sizes=sets,
        per_device_bytes=device_bytes(gs) // 4)
    check(sets == [4], f"sharded graph leaves sit on {sets} devices")
    key = jax.random.PRNGKey(seed)
    B = 4

    def runs(plan, graph):
        # analytics run on ``graph`` (prepared once for the mesh); the
        # engine prepares the raw compressed graph itself
        out = {}
        _, (p, lv), t_c, t_w = compile_and_run(
            lambda gg, s: bfs(gg, s, plan=plan), graph, jnp.int32(keys[0])
        )
        out["bfs"] = [(np.asarray(p), np.asarray(lv))]
        _, (pr, _), t_c2, t_w2 = compile_and_run(
            lambda gg: pagerank(gg, max_iters=PR_ITERS, eps=0.0, plan=plan), graph
        )
        out["pagerank"] = np.asarray(pr)
        _, cc, t_c3, t_w3 = compile_and_run(
            lambda gg, kk: connectivity(gg, kk, plan=plan), graph, key
        )
        out["cc"] = np.asarray(cc)
        eng = QueryEngine(c, plan=plan, max_batch=B)
        hs = [eng.submit("bfs", src=k) for k in keys[:B]]
        t0 = time.perf_counter()
        res = eng.flush()
        t_e = time.perf_counter() - t0
        out["engine"] = [tuple(np.asarray(a) for a in res[h]) for h in hs]
        out["times"] = dict(
            bfs_compile_s=round(t_c, 3), bfs_wall_s=round(t_w, 4),
            pagerank_compile_s=round(t_c2, 3), pagerank_wall_s=round(t_w2, 4),
            cc_compile_s=round(t_c3, 3), cc_wall_s=round(t_w3, 4),
            engine_flush_s_incl_compile=round(t_e, 3),
        )
        return out

    one = runs(single, c)
    say("single-chip", **one["times"])
    with use_mesh(mesh):
        four = runs(sharded, gs)
    say("four-chip", **four["times"], peak_bytes_in_use_dev0=peak_bytes())
    for (p1, l1), (p4, l4) in zip(one["bfs"], four["bfs"]):
        check(np.array_equal(p1, p4) and np.array_equal(l1, l4),
              "sharded BFS differs from single-chip")
    l1, vertex = check_pagerank(four["pagerank"], one["pagerank"],
                                "sharded PageRank vs single-chip")
    check(np.array_equal(one["cc"], four["cc"]), "sharded connectivity differs")
    for a, b in zip(one["engine"], four["engine"]):
        check(all(np.array_equal(x, y) for x, y in zip(a, b)),
              "sharded QueryEngine BFS differs from single-chip")
    say("four-chip:check", bfs_bit_identical=True, cc_bit_identical=True,
        engine_bit_identical=True,
        pagerank_l1_rel_diff=f"{l1:.3e}", pagerank_max_vertex_rel_diff=f"{vertex:.3e}")


def single_chip(scale, seed, nkeys):
    from concurrent.futures import ThreadPoolExecutor

    from repro.core import compress

    g = build_graph(scale, seed)
    keys = search_keys(g, nkeys, seed)
    requests = serve_requests(g, seed)
    host = host_view(g)
    with ThreadPoolExecutor(1) as pool:
        # the host oracles run while the chip works on the same graph
        refs = pool.submit(oracle_refs, host, keys)
        wref = pool.submit(wbfs_ref, host, requests)
        analytics("csr", g, keys, refs, seed)
        t0 = time.perf_counter()
        c = compress(g)
        say("compress", seconds=round(time.perf_counter() - t0, 3),
            exceptions=c.n_exceptions, ratio=round(c.compression_ratio, 3))
        del g  # the raw CSR leaves the chip: the compressed graph serves
        analytics("compressed", c, keys, refs, seed)
        serving(c, requests, wref)
    del c, host
    kernel_phase(seed)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=int, default=DEFAULT_SCALE,
                    help="Kronecker scale (log2 n)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--keys", type=int, default=4, help="BFS search keys")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.runtime import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform={dev.platform})", file=sys.stderr)
        return 2
    say("device", platform=dev.platform, kind=dev.device_kind,
        count=len(jax.devices()), compile_cache=cache)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips(args.scale, args.seed, args.keys)
        else:
            single_chip(args.scale, args.seed, args.keys)
    except Exception:  # noqa: BLE001 — any failed phase fails the smoke
        traceback.print_exc()
        return 1
    say("done", seconds=round(time.perf_counter() - t0, 3))
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
