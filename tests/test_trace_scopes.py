"""Named device scopes of the sweep and the decode, and the ingest's host
spans: where each lands, and that neither changes a result.

A device scope is ``op_name`` metadata of the compiled HLO, so the tests
read it from the compiled program's text; an ingest span records the
gauge ``sage_ingest_last_seconds{phase}`` when the registry is enabled.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.algorithms import bfs, bfs_batched, pagerank
from repro.core import build_csr, compress, edgemap_reduce
from repro.data import rmat_graph
from repro.obs import Registry, noop_registry, use_registry

PHASES = {"dedupe", "layout", "fetch", "encode"}


def _scope_paths(compiled_text: str) -> set:
    """The ``sage.*`` components of every op name, joined in order."""
    return {
        "/".join(p for p in name.split("/") if p.startswith("sage."))
        for name in re.findall(r'op_name="([^"]*)"', compiled_text)
    }


def _hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.fixture(scope="module")
def g():
    return rmat_graph(512, 4096, seed=1, block_size=32)


@pytest.fixture(scope="module")
def edges():
    rng = np.random.default_rng(5)
    return 300, rng.integers(0, 300, 2000), rng.integers(0, 300, 2000)


def test_bfs_auto_has_dense_and_sparse_sweeps_under_the_round(g):
    paths = _scope_paths(_hlo(lambda g, s: bfs(g, s, mode="auto"), g, jnp.int32(0)))
    assert {"sage.round/sage.sweep.dense", "sage.round/sage.sweep.sparse"} <= paths
    # every sweep op lies inside a round, and a raw CSR graph has no decode
    assert all(p.startswith("sage.round") for p in paths if "sage.sweep" in p)
    assert not any("sage.decode" in p for p in paths)


def test_pagerank_on_a_compressed_graph_decodes_under_the_dense_sweep(g):
    paths = _scope_paths(_hlo(lambda c: pagerank(c, max_iters=3), compress(g)))
    assert "sage.round/sage.sweep.dense/sage.decode" in paths
    assert not any("sage.sweep.sparse" in p for p in paths)


@pytest.mark.parametrize("mode", ["sparse", "sparse_streamed"])
def test_sparse_sweeps_decode_their_tiles_under_the_sparse_scope(g, mode):
    c = compress(g)
    front = jnp.zeros(c.n, bool).at[:8].set(True)
    x = jnp.arange(c.n, dtype=jnp.int32)
    paths = _scope_paths(_hlo(
        lambda c, f, x: edgemap_reduce(c, f, x, mode=mode, interpret=True), c, front, x))
    assert "sage.sweep.sparse/sage.decode" in paths
    assert not any("sage.sweep.dense" in p for p in paths)


def test_batched_bfs_names_both_sweeps(g):
    paths = _scope_paths(_hlo(lambda g, s: bfs_batched(g, s), g, jnp.arange(4, dtype=jnp.int32)))
    assert {"sage.round/sage.sweep.dense", "sage.round/sage.sweep.sparse"} <= paths


def test_ingest_records_its_four_phases(edges):
    n, src, dst = edges
    with use_registry(Registry()) as reg:
        compress(build_csr(n, src, dst, symmetrize=True, block_size=32))
    gauge = reg.get("sage_ingest_last_seconds")
    assert gauge is not None and gauge.label_names == ("phase",)
    assert {key[0] for key, _ in gauge.series()} == PHASES
    assert all(gauge.value(phase=p) >= 0.0 for p in PHASES)


def test_ingest_gauge_holds_the_last_ingest_not_a_sum(edges, monkeypatch):
    import repro.obs.trace as trace

    n, src, dst = edges
    clock = iter(float(t) for t in range(100))
    monkeypatch.setattr(trace, "perf_counter", lambda: next(clock))
    with use_registry(Registry()) as reg:
        build_csr(n, src, dst)
        build_csr(n, src, dst)
    # each phase reads the clock twice, one tick apart
    gauge = reg.get("sage_ingest_last_seconds")
    assert gauge.value(phase="dedupe") == 1.0 and gauge.value(phase="layout") == 1.0


def test_ingest_under_the_noop_registry_records_nothing(edges, monkeypatch):
    import repro.obs.trace as trace

    n, src, dst = edges

    def no_clock():
        raise AssertionError("a disabled registry read the clock")

    monkeypatch.setattr(trace, "perf_counter", no_clock)
    with use_registry(noop_registry()) as reg:
        compress(build_csr(n, src, dst, symmetrize=True))
        assert reg.get("sage_ingest_last_seconds") is None
        assert reg.snapshot() == {}


def test_results_bit_identical_with_the_registry_on_and_off(edges):
    n, src, dst = edges
    out = []
    for reg in (Registry(), noop_registry()):
        with use_registry(reg):
            csr = build_csr(n, src, dst, symmetrize=True, block_size=32)
            c = compress(csr)
            out.append([*jax.tree.leaves(csr), *jax.tree.leaves(c),
                        *bfs(csr, 3), *bfs(c, 3), *pagerank(c, max_iters=5)])
    assert len(out[0]) == len(out[1])
    for a, b in zip(*out):
        assert np.array_equal(np.asarray(a), np.asarray(b))
