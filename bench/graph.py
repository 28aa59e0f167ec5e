"""Set-up of a configuration's graph: generate on the device, then the
program's own ingest (``build_csr``, symmetrized and unweighted, and
``compress`` for the compressed layout), each step timed on the host clock
and ended by ``block_until_ready``."""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench import kronecker


@dataclasses.dataclass
class GraphInfo:
    """What the benchmark knows of the graph, for job streams and work."""

    n: int
    m_undirected: int          # the program's m (directed slots) / 2
    m_directed: int
    blocks: int
    exceptions: int | None
    has_edge: np.ndarray        # bool[n], from the generator's own tuples
    src: np.ndarray             # the generator's tuples, for the reference
    dst: np.ndarray


# every key a configuration file may hold: a key outside these would be a
# setting that no code follows
CONFIG_KEYS = {"name", "source", "generator", "layout", "block_size", "encoding",
               "reduced", "assumed"}
GENERATOR_KEYS = {"kind", "scale", "edgefactor", "a", "b", "c", "graph_seed"}


def build(config: dict, times: dict):
    """(program graph, GraphInfo); ``times`` gets each step's seconds."""
    import jax

    from repro.core import build_csr
    from repro.core.compressed import compress

    unknown = (set(config) - CONFIG_KEYS) | (set(config["generator"]) - GENERATOR_KEYS)
    if unknown:
        raise ValueError(f"configuration keys that nothing reads: {sorted(unknown)}")
    gen = config["generator"]
    if gen["kind"] != "kronecker":
        raise ValueError(f"unknown generator {gen['kind']!r}")
    t = time.perf_counter()
    src, dst, has_edge = jax.device_get(kronecker.kronecker_edges(
        gen["scale"], gen["edgefactor"], gen["a"], gen["b"], gen["c"],
        seed=gen["graph_seed"]))
    times["generate_s"] = time.perf_counter() - t
    n = 1 << gen["scale"]

    t = time.perf_counter()
    g = build_csr(n, src, dst, None, symmetrize=True, block_size=int(config["block_size"]))
    times["build_s"] = time.perf_counter() - t
    t = time.perf_counter()
    g = jax.block_until_ready(g)
    times["transfer_s"] = time.perf_counter() - t
    exceptions = None
    if config["layout"] == "compressed":
        t = time.perf_counter()
        g = jax.block_until_ready(compress(g))
        times["compress_s"] = time.perf_counter() - t
        exceptions = int(g.n_exceptions)
    elif config["layout"] != "csr":
        raise ValueError(f"unknown layout {config['layout']!r}")
    info = GraphInfo(n=int(g.n), m_undirected=int(g.m) // 2, m_directed=int(g.m),
                     blocks=int(g.num_blocks), exceptions=exceptions,
                     has_edge=np.asarray(has_edge), src=src, dst=dst)
    return g, info
