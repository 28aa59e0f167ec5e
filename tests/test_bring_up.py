"""What keeps the device visible: lowering rules on a TPU backend, the plan's
record of what its streamed rounds run and which table it planned from,
failing benches, CPU-pinned bench children, the compile-cache rule, and the
chip smoke refusing a host with no TPU."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import build_csr, compress, make_plan
from repro.data import rmat_graph
from repro.kernels import lowering
from repro.kernels.lowering import NATIVE_KERNELS, resolve_interpret, resolve_lowering

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer the lowering rules as a TPU backend would see them."""
    monkeypatch.setattr(lowering, "native_lowering_supported", lambda: True)


def test_tpu_backend_never_resolves_interpret(on_tpu):
    assert resolve_lowering() == "native"
    assert resolve_lowering("auto") == "native"
    with pytest.raises(ValueError, match="interpret"):
        resolve_lowering("interpret")
    with pytest.raises(ValueError, match="interpret"):
        resolve_interpret(True)


@pytest.mark.parametrize(
    "kernel",
    ["compressed_block_spmv", "compressed_chunked_sums", "edge_block_spmv", "filter_pack"],
)
def test_kernels_without_mosaic_lowering_refuse_native(kernel):
    assert kernel not in NATIVE_KERNELS
    with pytest.raises(NotImplementedError, match=kernel):
        resolve_lowering("native", kernel=kernel)
    # interpret mode stays available where the backend allows it
    assert resolve_lowering("interpret", kernel=kernel) == "interpret"


def test_streamed_plan_resolves_native_on_tpu(on_tpu):
    c = compress(rmat_graph(256, 1024, seed=3, block_size=32))
    plan = make_plan(c, strategy="sparse_streamed")
    assert plan.decisions.lowering == "native" and plan.interpret is False
    assert plan.decisions.streamed == "kernel"


def _exception_dense_graph():
    # every vertex links far past 2^16: the exception list outgrows its cap
    n = 140_000
    src = np.repeat(np.arange(40), 30)
    dst = (src * 3001 + np.tile(np.arange(30), 40) * 70_001) % n
    return compress(build_csr(n, src, dst, block_size=32))


@pytest.mark.parametrize(
    "graph, want",
    [
        (lambda: compress(rmat_graph(256, 1024, seed=3, block_size=32)), "kernel"),
        (_exception_dense_graph, "fallback:exception_dense"),
        (lambda: rmat_graph(256, 1024, seed=3, block_size=32), "fallback:no_decoder"),
    ],
    ids=["compressed", "exception_dense", "raw_csr"],
)
def test_plan_records_effective_streamed_mode(graph, want):
    from repro.core.compressed import exception_dense

    g = graph()
    if want == "fallback:exception_dense":
        assert exception_dense(g)
    plan = make_plan(g, strategy="sparse_streamed")
    assert plan.decisions.streamed == want
    assert f"streamed={want}" in plan.describe()
    # a plan that never streams records nothing
    assert make_plan(g, strategy="dense").decisions.streamed is None


def test_shipped_table_is_not_used_on_another_host(monkeypatch):
    from repro.tuning import measure
    from repro.tuning.table import constants_decision, default_table

    g = compress(rmat_graph(256, 1024, seed=3, block_size=32))
    assert make_plan(g).decisions.source == "measured"  # this host: cpu/cpu
    host = dict(measure.host_fingerprint(), platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(measure, "host_fingerprint", lambda: host)
    d = make_plan(g).decisions
    assert d.source == "host-mismatch"
    assert d.table_host == default_table().host_key
    want = constants_decision("compressed")
    assert (d.dense_frac, d.auto_sparse, d.auto_sparse_batched) == (
        want.dense_frac, want.auto_sparse, want.auto_sparse_batched
    )
    assert "source=host-mismatch" in make_plan(g).describe()


def test_hardware_model_knows_only_published_chips():
    from repro.tuning import hardware_model

    hw = hardware_model("TPU v5 lite")
    assert (hw["peak_flops"], hw["hbm_bw"], hw["hbm_bytes"]) == (197e12, 819e9, 16e9)
    with pytest.raises(KeyError, match="cpu"):
        hardware_model(jax.devices()[0].device_kind)


def test_bench_run_exits_nonzero_when_a_bench_fails(monkeypatch, capsys):
    from benchmarks import run, table5_edgemap

    def boom(**kw):
        raise RuntimeError("bench broke")

    monkeypatch.setattr(table5_edgemap, "run", boom)
    monkeypatch.setattr(run, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", ["run", "--only", "table5_edgemap"])
    with pytest.raises(SystemExit) as e:
        run.main()
    assert e.value.code == 1
    assert "table5_edgemap,-1,ERROR: RuntimeError: bench broke" in capsys.readouterr().out


def test_bench_children_run_on_host_cpu(monkeypatch):
    from benchmarks.cpu_child import run_cpu_child

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")  # what a chip host might carry
    code = "import json, os; print(json.dumps({'p': os.environ['JAX_PLATFORMS']}))"
    assert run_cpu_child(code) == {"p": "cpu"}
    with pytest.raises(RuntimeError, match="exited 3"):
        run_cpu_child("import sys; sys.exit(3)")


def test_compile_cache_placement(monkeypatch):
    from repro import runtime

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert runtime.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before  # left to JAX
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert runtime.enable_compile_cache() == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == runtime.CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(ROOT, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split(), ".jax_cache must be git-ignored"


def test_chip_smoke_refuses_a_host_without_tpu():
    r = subprocess.run(
        [sys.executable, "chip_smoke.py"],
        capture_output=True,
        text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        cwd=ROOT,
        timeout=120,
    )
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    for line in r.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_chip_smoke_pagerank_check_is_scale_free():
    """The smoke's relative PageRank bounds pass the engine's f32 result and
    refuse a result without the dangling mass or without one shard's edges
    (at scale 22 the tests' absolute 1e-5 is ~40x the mean rank)."""
    import importlib.util

    from repro.algorithms import pagerank

    sys.path.insert(0, os.path.join(ROOT, "tests"))
    import oracles

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py")
    )
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)

    g = rmat_graph(1 << 12, 16 << 12, seed=0)
    host = smoke.host_view(g)
    ref = oracles.pagerank_ref(host, iters=20, eps=0.0)
    pr, _ = pagerank(g, max_iters=20, eps=0.0, plan=make_plan(g))
    smoke.check_pagerank(np.asarray(pr), ref, "engine")

    s, d = host.edge_src, host.edge_dst
    keep = (d < g.n) & (np.arange(d.size) % 4 != 0)
    lost = oracles.pagerank_ref(
        type(host)(n=g.n, edge_src=s[keep], edge_dst=d[keep], edge_w=host.edge_w[keep]),
        iters=20, eps=0.0,
    )
    valid = d < g.n
    deg = np.bincount(s[valid], minlength=g.n)
    no_dangling = np.full(g.n, 1.0 / g.n)
    for _ in range(20):
        agg = np.bincount(d[valid], weights=(no_dangling / np.maximum(deg, 1))[s[valid]],
                          minlength=g.n)
        no_dangling = 0.15 / g.n + 0.85 * agg
    for wrong in (lost, no_dangling):
        with pytest.raises(smoke.SmokeError):
            smoke.check_pagerank(wrong, ref, "wrong")
