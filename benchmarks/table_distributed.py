"""Distributed planner benchmark: PageRank over sharded edges, raw vs
compressed (§5.2 at pod scale).

One PageRank round through the unified planner on a 4-way (fake CPU) mesh,
for both execution backends.  Reports per-shard edge throughput, the
compressed/raw wall-time ratio, and the PSAM per-shard read model
(``charge_edgemap_planned``) — the honest bytes-off-large-memory contrast
for the distributed path.  ``--full`` runs RMAT-20 (n = 2²⁰).

Runs in a host-CPU child process (``benchmarks.cpu_child``), so the
fake-device XLA flag doesn't leak into the parent and the child never
claims the parent's accelerator; every row says ``platform=cpu``.
"""
from __future__ import annotations

from .cpu_child import PLATFORM, run_cpu_child

CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys, time
import jax, jax.numpy as jnp
from repro.compat import make_mesh, use_mesh
from repro.core import PSAMCost, compress, make_plan
from repro.data import rmat_graph
from repro.distributed.engine import distributed_pagerank_step, prepare_sharded

n, m = int(sys.argv[1]), int(sys.argv[2])
mesh = make_mesh((4,), ("data",))
S = int(mesh.devices.size)
g = rmat_graph(n, m, seed=20, block_size=32)
c = compress(g)
pr = jnp.full(g.n, 1.0 / g.n)
inv = jnp.where(g.degrees > 0, 1.0 / jnp.maximum(g.degrees, 1).astype(jnp.float32), 0.0)
step = distributed_pagerank_step(mesh, n=g.n)

out = {"n": g.n, "m": g.m, "shards": S, "ratio": c.compression_ratio}
with use_mesh(mesh):
    for label, backend in [("raw", g), ("compressed", c)]:
        gs = prepare_sharded(mesh, backend)
        fn = jax.jit(step)
        fn(gs, pr, inv).block_until_ready()  # compile + warmup
        reps = 3
        t0 = time.perf_counter()
        for _ in range(reps):
            fn(gs, pr, inv).block_until_ready()
        us = (time.perf_counter() - t0) * 1e6 / reps
        cost = PSAMCost()
        cost.charge_edgemap_planned(backend, num_shards=S)
        out[label] = {
            "us": us,
            "edges_per_s_per_shard": g.m / (us * 1e-6) / S,
            "psam_read_words": cost.large_reads,
        }
print(json.dumps(out))
"""


def run(n=4096, m=16384):
    d = run_cpu_child(CODE, n, m)
    rows = []
    for label in ["raw", "compressed"]:
        rows.append(
            dict(
                name=f"table_distributed_pagerank_{label}",
                us_per_call=d[label]["us"],
                derived=(
                    f"edges_per_s_per_shard={d[label]['edges_per_s_per_shard']:.0f} "
                    f"psam_read_words={d[label]['psam_read_words']} "
                    f"shards={d['shards']} n={d['n']} m={d['m']} "
                    f"platform={PLATFORM}"
                ),
            )
        )
    rows.append(
        dict(
            name="table_distributed_compressed_vs_raw",
            us_per_call=0,
            derived=(
                f"us_ratio={d['compressed']['us'] / max(d['raw']['us'], 1e-9):.2f} "
                f"psam_read_saving="
                f"{d['raw']['psam_read_words'] / max(d['compressed']['psam_read_words'], 1):.2f}x "
                f"compression_ratio={d['ratio']:.2f}x platform={PLATFORM}"
            ),
        )
    )
    return rows


if __name__ == "__main__":
    for r in run():
        print(f"{r['name']},{r['us_per_call']:.0f},{r['derived']}")
