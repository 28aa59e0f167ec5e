"""Graph500 Kronecker edge generator, on the device, from a seed.

One jitted call draws every edge: for each of ``scale`` levels a 32-bit
word per edge picks one quadrant of the initiator matrix [[A, B], [C, D]]
(thresholds on the raw bits, so the draw is exact integer arithmetic and
gives the same edges on any backend).  Quadrant q sets the level's source
bit to ``q >= 2`` and its target bit to ``q & 1``, as the Graph500
specification's "Graph Generation" section and ``repro.data.rmat_edges``
do.  The vertex labels are then permuted at random, as the specification
asks, so that a label says nothing of a vertex's degree or neighbours.
(The specification also shuffles the edge tuples; their order is already
random here, and the ingest sorts them.)  Self-loops and duplicates are
left in: the program's ingest drops them, and so does the plain reference.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any whole number: its low 64 bits, as two words."""
    s = int(seed) % (1 << 64)
    data = np.array([s >> 32, s & 0xFFFFFFFF], dtype=np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(data), impl="threefry2x32")


def thresholds(a: float, b: float, c: float) -> np.ndarray:
    """Cumulative quadrant probabilities as uint32 bounds on a raw word."""
    cum = np.cumsum([a, b, c])
    return np.minimum(np.floor(cum * 2.0**32), 2.0**32 - 1).astype(np.uint32)


def levels(key, bounds, scale: int, num_edges: int):
    """The edge tuples before relabeling: one quadrant per level."""
    def level(i, sd):
        src, dst = sd
        u = jax.random.bits(jax.random.fold_in(key, i), (num_edges,), jnp.uint32)
        q = (
            (u >= bounds[0]).astype(jnp.int32)
            + (u >= bounds[1]).astype(jnp.int32)
            + (u >= bounds[2]).astype(jnp.int32)
        )
        return src * 2 + (q >> 1), dst * 2 + (q & 1)

    zero = jnp.zeros(num_edges, jnp.int32)
    return jax.lax.fori_loop(0, scale, level, (zero, zero))


def labels(key, scale: int):
    """The random relabeling, int32[2**scale]: the vertices sorted by a
    random word, ties broken by label, so the order is exact on any backend."""
    n = 1 << scale
    words = jax.random.bits(jax.random.fold_in(key, scale), (n,), jnp.uint32)
    return jax.lax.sort((words, jnp.arange(n, dtype=jnp.int32)), num_keys=2)[1]


@partial(jax.jit, static_argnames=("scale", "num_edges"))
def _edges(key, bounds, *, scale: int, num_edges: int):
    src, dst = levels(key, bounds, scale, num_edges)
    perm = labels(key, scale)
    src, dst = perm[src], perm[dst]
    n = 1 << scale
    loop = src == dst
    # a vertex has an edge after ingest iff it ends some non-loop tuple
    has_edge = (
        jnp.zeros(n, bool)
        .at[jnp.where(loop, n, src)].set(True, mode="drop")
        .at[jnp.where(loop, n, dst)].set(True, mode="drop")
    )
    return src, dst, has_edge


def kronecker_edges(scale: int, edgefactor: int, a: float, b: float, c: float,
                    seed: int):
    """(src, dst, has_edge) as device arrays: ``edgefactor * 2**scale`` int32
    edge tuples over permuted labels and a bool[2**scale] mask of the
    vertices that keep an edge once self-loops are dropped."""
    bounds = jnp.asarray(thresholds(a, b, c))
    return _edges(seed_key(seed), bounds, scale=scale,
                  num_edges=edgefactor << scale)
