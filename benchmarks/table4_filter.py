"""Paper Table 4: graph-filter block size F_B vs triangle-counting work,
plus the planner-native filtered-edgeMap columns.

The paper measures intersection work (fixed per ordering) against total
block-decode work, which grows with F_B because fetching one active edge
decodes the whole block.  We reproduce both columns analytically from the
filter structure plus the measured running time.

``run_planned`` adds the columns the filter story gained with the unified
planner: the same filtered aggregation through (a) the raw-CSR Pallas
kernel with the packed ``edge_active`` operand, (b) the compressed kernel
(bitmask ANDed in-VMEM next to the fused delta decode), and (c) a 4-shard
fake-CPU mesh where the filter words shard block-range-wise alongside the
edge blocks.  Derived columns report the live-block count and the PSAM
filtered read model (``charge_edgemap_planned(filter_live_blocks=...)``).
"""
from __future__ import annotations

import time

import numpy as np

from repro.algorithms.substructure import orientation_filter, triangle_count
from repro.data import rmat_graph

from .cpu_child import PLATFORM, run_cpu_child


def run(n=2048, m=16384, block_sizes=(32, 64, 128, 256)):
    rows = []
    for fb in block_sizes:
        g = rmat_graph(n, m, seed=1, block_size=fb)
        f, keep = orientation_filter(g)
        # intersection work: sum over directed edges of min(d+(u), d+(v))
        src = np.asarray(g.edge_src)
        dst = np.asarray(g.edge_dst)
        deg_or = np.asarray(f.active_deg)
        us, vs = src[keep], dst[keep]
        inter_work = int(np.minimum(deg_or[us], deg_or[vs]).sum())
        # total decode work: every touched block decodes F_B slots
        blocks_live = int(np.asarray(f.block_live).sum())
        total_work = blocks_live * fb
        t0 = time.perf_counter()
        tri = triangle_count(g)
        dt = time.perf_counter() - t0
        rows.append(
            dict(
                name=f"table4_fb{fb}",
                us_per_call=dt * 1e6,
                derived=(
                    f"F_B={fb} intersection_work={inter_work} "
                    f"decode_work={total_work} triangles={tri}"
                ),
            )
        )
    return rows


_MESH_CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys, time
import jax, jax.numpy as jnp, numpy as np
from repro.compat import make_mesh, use_mesh
from repro.core import compress, make_plan, edgemap_reduce
from repro.algorithms.substructure import orientation_filter
from repro.data import rmat_graph

n, m = int(sys.argv[1]), int(sys.argv[2])
g = rmat_graph(n, m, seed=1, block_size=32)
c = compress(g)
f, _ = orientation_filter(g)
x = jnp.ones(g.n, jnp.float32)
full = jnp.ones(g.n, bool)
mesh = make_mesh((4,), ("data",))
out = {}
with use_mesh(mesh):
    for label, backend in [("csr", g), ("compressed", c)]:
        plan = make_plan(backend, mesh=mesh)
        gs, sea = plan.prepare(backend, edge_active=f)

        @jax.jit
        def step(gss, xv, ea):
            o, _ = edgemap_reduce(
                gss, full, xv, monoid="sum", edge_active=ea,
                mode="dense", plan=plan,
            )
            return o

        step(gs, x, sea).block_until_ready()  # compile + warmup
        reps = 5
        t0 = time.perf_counter()
        for _ in range(reps):
            step(gs, x, sea).block_until_ready()
        out[label] = (time.perf_counter() - t0) * 1e6 / reps
print(json.dumps(out))
"""


def run_planned(n=512, m=4096):
    """Planner-native filtered-edgeMap columns (kernel operand + 4-shard mesh)."""
    import jax
    import jax.numpy as jnp

    from repro.core import PSAMCost, compress, make_filter
    from repro.kernels import compressed_spmv_vertex, spmv_vertex

    g = rmat_graph(n, m, seed=1, block_size=32)
    c = compress(g)
    f, _ = orientation_filter(g)
    live = int(np.asarray(f.block_live).sum())
    base = make_filter(g)
    x = jnp.ones(g.n, jnp.float32)

    def timed(fn):
        jax.block_until_ready(fn())  # compile + warmup
        reps = 5
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(fn())
        return (time.perf_counter() - t0) * 1e6 / reps

    rows = []
    for label, fn, backend in [
        ("csr", lambda: spmv_vertex(g, x, base, edge_active=f), g),
        (
            "compressed",
            lambda: compressed_spmv_vertex(c, x, base, edge_active=f),
            c,
        ),
    ]:
        cost = PSAMCost()
        cost.charge_edgemap_planned(backend, num_shards=1, filter_live_blocks=live)
        rows.append(
            dict(
                name=f"table4_filtered_kernel_{label}",
                us_per_call=timed(fn),
                derived=(
                    f"live_blocks={live}/{g.num_blocks} "
                    f"psam_filtered_read_words={cost.large_reads}"
                ),
            )
        )

    # 4-shard mesh columns run in a host-CPU child process
    mesh_us = run_cpu_child(_MESH_CODE, n, m)
    for label in ["csr", "compressed"]:
        cost = PSAMCost()
        backend = c if label == "compressed" else g
        cost.charge_edgemap_planned(backend, num_shards=4, filter_live_blocks=live)
        rows.append(
            dict(
                name=f"table4_filtered_mesh4_{label}",
                us_per_call=mesh_us[label],
                derived=(
                    f"shards=4 live_blocks={live}/{g.num_blocks} "
                    f"psam_filtered_read_words={cost.large_reads} "
                    f"platform={PLATFORM}"
                ),
            )
        )
    return rows


if __name__ == "__main__":
    for r in run() + run_planned():
        print(f"{r['name']},{r['us_per_call']:.0f},{r['derived']}")
