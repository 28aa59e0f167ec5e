"""Compiles for a described TPU v5e chip at the smoke's real shapes.

No chip is attached: the TPU compiler installed with JAX compiles for a
chip described by ``jax.experimental.topologies``, and refuses what the chip
would refuse — a kernel Mosaic cannot lower, a program that does not fit
HBM.  The shapes are those of ``chip_smoke.py``'s default graph (Graph500
Kronecker scale 21, F_B = 128) and of its serving width.  Nothing here runs,
so these tests say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library at a time, and every test worker imports
this file.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import CompressedCSR
from repro.core.csr import graph_spec
from repro.core.edgemap import edgemap_reduce, edgemap_reduce_batched
from repro.kernels.compressed_spmv.compressed_spmv import (
    compressed_chunked_spmv_pallas,
)
from repro.tuning.defaults import (
    DEFAULT_CHUNK_BLOCKS,
    DEFAULT_TILE_BLOCKS,
    HARDWARE_PEAKS,
)

SCALE = 21
N = 1 << SCALE
FB = 128
# rmat_graph(2**21, 16 * 2**21, weighted=True, seed=0) as chip_smoke.py
# builds and reports it
NUM_BLOCKS = 1_578_479
NUM_EXCEPTIONS = 3_985_003
SERVE_WIDTH = 8  # the widest cohort chip_smoke.py found fitting at scale 21
HBM = HARDWARE_PEAKS["TPU v5 lite"]["hbm_bytes"]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # the installed libtpu describes v5e: a failure here is a fault, not a skip
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _csr(sharding):
    return jax.tree.map(
        lambda s: _spec(s.shape, s.dtype, sharding),
        graph_spec(N, NUM_BLOCKS, FB, weighted=True),
    )


def _compressed(sharding):
    s = lambda shape, dt: _spec(shape, dt, sharding)  # noqa: E731
    return CompressedCSR(
        block_first=s((NUM_BLOCKS,), jnp.int32),
        deltas=s((NUM_BLOCKS, FB), jnp.uint16),
        valid_count=s((NUM_BLOCKS,), jnp.uint16),
        exc_block=s((NUM_EXCEPTIONS,), jnp.int32),
        exc_slot=s((NUM_EXCEPTIONS,), jnp.int32),
        exc_value=s((NUM_EXCEPTIONS,), jnp.int32),
        block_src=s((NUM_BLOCKS,), jnp.int32),
        degrees=s((N,), jnp.int32),
        n=N,
        m=NUM_BLOCKS * FB // 4,
        num_blocks=NUM_BLOCKS,
        block_size=FB,
        n_exceptions=NUM_EXCEPTIONS,
        block_weights=s((NUM_BLOCKS, FB), jnp.float32),
        weighted=True,
    )


def _footprint(compiled) -> int:
    ma = compiled.memory_analysis()
    return int(
        ma.argument_size_in_bytes + ma.temp_size_in_bytes + ma.output_size_in_bytes
    )


@pytest.mark.parametrize("filtered", [False, True], ids=["plain", "edge_active"])
@pytest.mark.parametrize("weighted", [False, True], ids=["unweighted", "weighted"])
def test_streamed_decode_kernel_lowers_natively(one_chip, filtered, weighted):
    """The sparse_streamed rounds' kernel: Mosaic lowers it at the smoke's
    block count, with and without the packed traversal mask and the weight
    stream, and the program holds the Pallas custom call."""
    s = lambda shape, dt: _spec(shape, dt, one_chip)  # noqa: E731
    active = s((NUM_BLOCKS, FB // 32), jnp.uint32) if filtered else None
    weights = s((NUM_BLOCKS, FB), jnp.float32) if weighted else None
    compiled = jax.jit(
        lambda ids, first, deltas, vc, act, w: compressed_chunked_spmv_pallas(
            None, ids, first, deltas, vc, None, act, w, n=N, emit="decode",
            tile_blocks=DEFAULT_TILE_BLOCKS, interpret=False,
        )
    ).lower(
        s((DEFAULT_CHUNK_BLOCKS,), jnp.int32),
        s((NUM_BLOCKS,), jnp.int32),
        s((NUM_BLOCKS, FB), jnp.uint16),
        s((NUM_BLOCKS,), jnp.uint16),
        active,
        weights,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the chunk gather reads live rows only: no copy of the compressed arrays
    assert compiled.memory_analysis().temp_size_in_bytes < NUM_BLOCKS * FB * 2


def test_dense_edgemap_round_fits_one_chip(one_chip):
    """One jitted dense edgeMap round over the raw CSR graph."""
    g = _csr(one_chip)
    compiled = jax.jit(
        lambda gg, fr, x: edgemap_reduce(gg, fr, x, monoid="min", mode="dense")
    ).lower(
        g, _spec((N,), jnp.bool_, one_chip), _spec((N,), jnp.int32, one_chip)
    ).compile()
    assert _footprint(compiled) <= HBM


def test_batched_dense_round_fits_at_serving_width(one_chip):
    """One batched dense round over the compressed graph at the serving
    width, the serving cohort's widest per-round program."""
    c = _compressed(one_chip)
    B = SERVE_WIDTH
    compiled = jax.jit(
        lambda gg, fr, x: edgemap_reduce_batched(
            gg, fr, x, monoid="min", mode="dense"
        )
    ).lower(
        c,
        _spec((B, N), jnp.bool_, one_chip),
        _spec((B, N), jnp.int32, one_chip),
    ).compile()
    assert _footprint(compiled) <= HBM


def _while_bodies(hlo: str) -> dict[str, list[str]]:
    """Each while-loop body computation of an HLO module: name -> lines."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        if line and not line.startswith(" ") and line.rstrip().endswith("{"):
            cur = line.split(" ")[0]
            comps[cur] = []
        elif cur is not None:
            comps[cur].append(line)
    return {b: comps[b] for b in set(re.findall(r"body=(%[\w.\-]+)", hlo))}


def test_sparse_round_decodes_outside_the_chunk_loop(one_chip):
    """A sparse edgeMap on an exception-dense compressed graph decodes the
    whole graph once per call: the chunk loop's body computes nothing of
    the decoded (NB, F_B) shape, it only carries it.  XLA for TPU does not
    hoist that decode itself."""
    c = _compressed(one_chip)
    compiled = jax.jit(
        lambda gg, fr, x: edgemap_reduce(gg, fr, x, monoid="min", mode="sparse")
    ).lower(
        c, _spec((N,), jnp.bool_, one_chip), _spec((N,), jnp.int32, one_chip)
    ).compile()
    bodies = _while_bodies(compiled.as_text())
    assert bodies
    full = re.compile(rf"= s32\[{NUM_BLOCKS},{FB}\]\S* (\S+?)\(")
    for name, lines in bodies.items():
        ops = {m.group(1) for line in lines if (m := full.search(line))}
        assert ops <= {"parameter", "get-tuple-element"}, (name, ops)
