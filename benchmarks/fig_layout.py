"""Paper §5.2: graph layout / replication microbenchmark, at pod scale.

The paper found cross-socket NVRAM reads 3.7× slower and fixed it by
replicating the graph per socket.  The pod-scale analogue: cross-pod edge
traffic must be avoided by making the 'pod' axis a pure replica axis.  We
compare the collective bytes (from compiled HLO) of one distributed
vertex-reduce round under (a) edges sharded across ALL axes including pod —
cross-pod psum carries the O(n) vertex vector per axis; (b) the engine's
layout where the pod axis only ever reduces O(n) words once.

On 8 fake CPU devices (2 pods × 4) in a host-CPU child process; the
metric is compile-derived bytes, not wall time.
"""
from __future__ import annotations

import time

from .cpu_child import PLATFORM, run_cpu_child


def run():
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.data import rmat_graph
from repro.distributed.engine import distributed_vertex_reduce, prepare_sharded
from repro.launch.dryrun import collective_bytes_from_hlo
from repro.compat import make_mesh, use_mesh
import json

g = rmat_graph(1024, 8192, seed=0, block_size=64)
out = {}
for name, shape, axes in [
    ("edges_sharded_all_axes", (2, 4), ("pod", "data")),
    ("single_axis_flat", (8,), ("data",)),
]:
    mesh = make_mesh(shape, axes)
    gs = prepare_sharded(mesh, g)
    fn = distributed_vertex_reduce(mesh, n=g.n)
    x = jnp.ones(g.n, jnp.float32)
    with use_mesh(mesh):
        compiled = jax.jit(fn).lower(gs, x).compile()
    coll = collective_bytes_from_hlo(compiled.as_text())
    out[name] = coll["total"]
print(json.dumps(out))
"""
    t0 = time.perf_counter()
    data = run_cpu_child(code)
    dt = time.perf_counter() - t0
    return [
        dict(
            name=f"fig_layout_{k}",
            us_per_call=dt * 1e6 / max(len(data), 1),
            derived=f"collective_bytes_per_round={v} platform={PLATFORM}",
        )
        for k, v in data.items()
    ]


if __name__ == "__main__":
    for r in run():
        print(f"{r['name']},{r['us_per_call']:.0f},{r['derived']}")
