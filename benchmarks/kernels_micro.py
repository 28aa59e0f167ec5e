"""Kernel microbenchmarks: the Pallas kernels (interpret mode on CPU; on a
TPU only the streamed decode lowers natively, the rest refuse) against their
pure-jnp references, plus the frontier-sweep row demonstrating the
chunked-mode kernel's PSAM read model: streamed bytes proportional to the
live (frontier-owned) blocks, never to NB."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import PSAMCost, compress, make_filter
from repro.data import rmat_graph
from repro.kernels import (
    compressed_spmv_vertex,
    compressed_spmv_vertex_chunked,
    embedding_bag,
    spmv_vertex,
)
from repro.kernels.compressed_spmv.ops import compressed_chunked_stream_tile
from repro.kernels.compressed_spmv.ref import compressed_chunked_spmv_ref
from repro.kernels.edge_block_spmv.ref import spmv_vertex_ref
from repro.kernels.embedding_bag.ref import embedding_bag_ref
from repro.kernels.lowering import resolve_lowering


def _timeit(fn, *args):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) * 1e6


def frontier_stream_derived(c, k: int, tile_blocks: int) -> str:
    """PSAM read model of one frontier-sparse streamed round, as a derived
    string: streamed (chunk-padded live), exactly-live and dense-NB words.

    Shared by the `kernels_micro` and `table_compression` frontier-sweep
    rows so the acceptance ratio (streamed ≤ 1.2× live at 10% density) is
    computed exactly one way.
    """
    streamed, live, dense = PSAMCost(), PSAMCost(), PSAMCost()
    streamed.charge_edgemap_sparse(c, k, tile_blocks=tile_blocks)
    live.charge_edgemap_sparse(c, k, tile_blocks=1)
    dense.charge_edgemap_dense(c)
    return (
        f"live_blocks={k}/{c.num_blocks} "
        f"streamed_words={streamed.large_reads} "
        f"live_words={live.large_reads} "
        f"dense_words={dense.large_reads} "
        f"streamed_vs_live={streamed.large_reads / max(live.large_reads, 1):.3f}x "
        f"dense_vs_streamed={dense.large_reads / max(streamed.large_reads, 1):.1f}x"
    )


def obs_overhead_row(reps: int = 15):
    """Instrumented-vs-disabled overhead of one graph ingest.

    The observability layer's overhead bar as a bench row: the same
    ``build_csr`` + ``compress`` of one edge list timed (min over ``reps``)
    under an enabled ``Registry`` and under ``noop_registry()``.  Each of
    the four ingest phases opens a host span and, when enabled, reads the
    clock twice and sets one gauge series, so the ratio must stay under 1.03 —
    asserted HERE, in the bench, so any hot-path instrumentation creep
    fails CI rather than drifting the trend.
    """
    from repro.core import build_csr
    from repro.obs import Registry, noop_registry, use_registry

    rng = np.random.default_rng(1)
    n = 1 << 14
    src = rng.integers(0, n, 1 << 18)
    dst = rng.integers(0, n, 1 << 18)

    def ingest():
        return jax.block_until_ready(
            compress(build_csr(n, src, dst, symmetrize=True, block_size=64))
        )

    def timed(reg):
        with use_registry(reg):
            t0 = time.perf_counter()
            ingest()
            return time.perf_counter() - t0

    # legs interleaved, first one then the other first, so that a busy
    # host slows both alike
    regs = [("on", Registry()), ("off", noop_registry())]
    for _, reg in regs:
        timed(reg)  # warmup: allocator and op caches hot before timing
    best = {k: float("inf") for k, _ in regs}
    for i in range(reps):
        for k, reg in regs if i % 2 == 0 else regs[::-1]:
            best[k] = min(best[k], timed(reg))
    on, off = best["on"] * 1e6, best["off"] * 1e6
    ratio = on / max(off, 1e-9)
    assert ratio < 1.03, (
        f"obs overhead {ratio:.3f}x >= 1.03x on build_csr + compress "
        f"(enabled {on:.0f}us vs disabled {off:.0f}us)"
    )
    return dict(
        name="ingest_obs_overhead",
        us_per_call=on,
        derived=(
            f"enabled={on:.0f}us disabled={off:.0f}us ratio={ratio:.3f}x "
            f"(<1.03x enforced in-bench)"
        ),
    )


def run():
    rows = []
    g = rmat_graph(1024, 8192, weighted=True, seed=1, block_size=64)
    f = make_filter(g)
    x = jax.random.normal(jax.random.PRNGKey(0), (g.n,), jnp.float32)
    rows.append(
        dict(name="spmv_pallas_interp", us_per_call=_timeit(lambda: spmv_vertex(g, x, f)),
             derived=f"NB={g.num_blocks} FB={g.block_size}")
    )
    ref = jax.jit(
        lambda xx: spmv_vertex_ref(xx, g.block_dst, g.block_w, f.bits, g.block_src, n=g.n)
    )
    rows.append(dict(name="spmv_jnp_ref", us_per_call=_timeit(ref, x), derived="oracle"))

    # ------------------------------------------------------------------
    # Lowering seam: the same kernel under forced interpret mode vs the
    # per-backend resolved default (identical on CPU, native on TPU) — the
    # trend pair that shows what the auto decision buys on each host
    # ------------------------------------------------------------------
    rows.append(
        dict(
            name="spmv_lowering_forced_interp",
            us_per_call=_timeit(lambda: spmv_vertex(g, x, f, interpret=True)),
            derived="interpret pinned",
        )
    )
    rows.append(
        dict(
            name="spmv_lowering_resolved",
            us_per_call=_timeit(lambda: spmv_vertex(g, x, f, interpret=None)),
            derived=f"resolved={resolve_lowering()}",
        )
    )

    # ------------------------------------------------------------------
    # Frontier sweep (the chunked PrefetchScalarGridSpec mode): a 10%-dense
    # frontier must stream ≤ 1.2× the live blocks' bytes — the read volume
    # tracks the compacted live-id list the kernel's index_maps walk, not NB
    # ------------------------------------------------------------------
    TB = 8
    c = compress(g)
    rng = np.random.default_rng(0)
    frontier = jnp.asarray(rng.random(g.n) < 0.10)
    blk_live = jnp.take(frontier, c.block_src, mode="fill", fill_value=False)
    k = int(blk_live.sum())
    us_chunk = _timeit(
        lambda: compressed_spmv_vertex_chunked(c, x, frontier, f, tile_blocks=TB)
    )
    rows.append(
        dict(
            name="spmv_chunked_frontier_sweep",
            us_per_call=us_chunk,
            derived=frontier_stream_derived(c, k, TB),
        )
    )
    # gather-tile shape: the (1, F_B) row-wise PrefetchScalarGridSpec walk
    # vs the default (TB, F_B) pre-gathered DMA tiles, on ONE streamed
    # decode of the 10%-frontier's live blocks — same rows read, same PSAM
    # charge, batched HBM→VMEM transfers (acceptance: tiled ≥ 1.3×)
    live_ids = jnp.nonzero(blk_live)[0].astype(jnp.int32)
    us_rowwise = _timeit(
        lambda: compressed_chunked_stream_tile(
            c, live_ids, f, tile_blocks=TB, gather_tiles=False
        )
    )
    rows.append(
        dict(
            name="stream_tile_rowwise_gather",
            us_per_call=us_rowwise,
            derived="(1,FB) scalar-prefetch rows",
        )
    )
    us_tiled = _timeit(
        lambda: compressed_chunked_stream_tile(
            c, live_ids, f, tile_blocks=TB, gather_tiles=True
        )
    )
    rows.append(
        dict(
            name="stream_tile_tiled_gather",
            us_per_call=us_tiled,
            derived=(
                f"(TB,FB) pre-gathered tiles TB={TB} "
                f"speedup_vs_rowwise={us_rowwise / max(us_tiled, 1e-9):.2f}x"
            ),
        )
    )
    ref_chunk = jax.jit(
        lambda xx: compressed_chunked_spmv_ref(c, xx, frontier, f.bits, c.block_weights)
    )
    rows.append(
        dict(
            name="spmv_chunked_frontier_jnp_ref",
            us_per_call=_timeit(ref_chunk, x),
            derived="oracle (masked full stream)",
        )
    )
    rows.append(
        dict(
            name="spmv_compressed_dense_grid",
            us_per_call=_timeit(lambda: compressed_spmv_vertex(c, x, f)),
            derived="every block streams (the dense-mode kernel, for contrast)",
        )
    )

    table = jax.random.normal(jax.random.PRNGKey(1), (4096, 64), jnp.float32)
    idx = jax.random.randint(jax.random.PRNGKey(2), (512, 16), -1, 4096)
    w = jnp.ones((512, 16), jnp.float32)
    rows.append(
        dict(name="embedding_bag_pallas_interp",
             us_per_call=_timeit(lambda: embedding_bag(table, idx, w)),
             derived="V=4096 D=64 B=512 L=16")
    )
    refb = jax.jit(lambda t, i, ww: embedding_bag_ref(t, i, ww))
    rows.append(
        dict(name="embedding_bag_jnp_ref", us_per_call=_timeit(refb, table, idx, w),
             derived="oracle")
    )
    rows.append(obs_overhead_row())
    return rows


if __name__ == "__main__":
    import sys

    if "--obs-overhead" in sys.argv:
        # CI's dedicated overhead gate: just the instrumented-vs-disabled
        # row (its <1.03x assert IS the check), no other kernels timed
        r = obs_overhead_row()
        print(f"{r['name']},{r['us_per_call']:.0f},{r['derived']}")
    else:
        for r in run():
            print(f"{r['name']},{r['us_per_call']:.0f},{r['derived']}")
