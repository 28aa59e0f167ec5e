"""calibrate() — microbenchmark every tunable knob and emit a TuningTable.

One pass, one synthetic R-MAT workload (same generator the benchmark tables
use), both backends:

* **Density sweep** — for a grid of frontier sizes, time one jitted
  ``edgemap_reduce`` round per fixed strategy (``dense``, ``sparse``, and
  ``sparse_streamed`` where the backend has a streaming decoder) and record
  the *measured* edge density ``sum_deg(frontier) / m`` next to each
  sample.  The dense/sparse wall-time crossover of this sweep is what
  replaces the Beamer ``dense_frac = 20`` constant
  (``dense_frac = 1 / d*``).
* **Chunk sweep** — at a mid-grid density, time the sparse path across
  ``chunk_blocks`` candidates; the argmin becomes the plan's chunk size.
* **Batch sweep** — time ``edgemap_reduce_batched`` across widths B and
  take the knee of the per-query cost curve (the smallest B within 10 % of
  the best amortization) as the serving ``max_batch``.
* **Batched density sweep** — the same per-strategy grid at batch width
  B=8 through ``edgemap_reduce_batched``.  Nothing transfers from the
  single-query sweep: the batched dense body is one shared sweep for all
  lanes (its crossover → ``dense_frac_batched``), and the streamed union
  runs one live-block loop shared by all lanes (its streamed/plain flip →
  ``batched_flavor_crossover``, the density where batched auto switches
  sparse flavor at runtime).
* **Tile sweep** (compressed backend, full mode only) — time the Pallas
  ``compressed_spmv_vertex`` kernel across TB tile candidates.
* **Shard sweep** (full mode, multi-device hosts only) — time a mesh plan
  per shard count.

Timing discipline: every variant is jitted, warmed up once (compile time
excluded), then timed as the **minimum** over ``reps`` block-until-ready
calls — min, not mean, because calibration wants the contention-free cost.
Modeled read words ride along with each sample (``edgemap_round_read_words``
scaled by the active-block fraction for the sparse side) so the table can
price NVRAM traffic, not just wall time.

jax / repro.core are imported lazily inside the functions: ``repro.core``
imports ``repro.tuning.defaults`` at module load, and this module must not
close that loop at import time.
"""
from __future__ import annotations

import platform
import sys
import time

from .defaults import (
    DEFAULT_CHUNK_BLOCKS,
    DEFAULT_MAX_BATCH,
    DEFAULT_TILE_BLOCKS,
)
from .table import (
    SCHEMA_VERSION,
    TuningTable,
    crossover_from_sweep,
    dense_frac_from_crossover,
    flavor_crossover_from_sweep,
)

# Frontier sizes as vertex fractions: spans BFS's first lonely round
# through the saturated mid-traversal rounds.
_DENSITY_GRID = (0.002, 0.01, 0.05, 0.2, 1.0)
_DENSITY_GRID_QUICK = (0.002, 0.05, 1.0)
_CHUNK_GRID = (64, 128, 256, 512)
_CHUNK_GRID_QUICK = (128, 256)
_BATCH_GRID = (1, 2, 4, 8, 16)
_BATCH_GRID_QUICK = (1, 4, 8)
_TILE_GRID = (4, 8, 16)


def host_fingerprint() -> dict:
    """Identity of the machine a table was measured on (keys the table)."""
    import jax

    dev = jax.devices()[0]
    return {
        "platform": jax.default_backend(),
        "device_kind": dev.device_kind,
        "device_count": jax.device_count(),
        "machine": platform.machine(),
        "python": sys.version.split()[0],
    }


def _time_us(fn, *args, reps: int = 3) -> float:
    """Min-of-reps wall time (us) of an already-jitted fn, post-warmup."""
    import jax

    out = fn(*args)
    jax.block_until_ready(out)  # warmup: compile + first run excluded
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _frontier_for_fraction(g, frac: float, seed: int):
    """bool[n] mask selecting ~frac of vertices (deterministic per seed)."""
    import numpy as np

    n = g.n
    k = max(1, min(n, int(round(frac * n))))
    rng = np.random.default_rng(seed)
    idx = rng.choice(n, size=k, replace=False)
    mask = np.zeros(n, dtype=bool)
    mask[idx] = True
    return mask


def _measured_density(g, mask) -> float:
    """The quantity auto's predicate tests: frontier incident edges / m."""
    import numpy as np

    deg = np.asarray(g.degrees)
    return float(np.sum(np.where(mask, deg, 0))) / max(1, int(g.m))


def _active_block_fraction(g, mask) -> float:
    import numpy as np

    src = np.asarray(g.block_src)
    n = g.n
    live = src < n
    if not live.any():
        return 0.0
    return float(np.sum(mask[src[live]])) / float(np.sum(live))


def _has_streaming(g) -> bool:
    from ..core.edgemap import _streaming_decoder

    return _streaming_decoder(g, None) is not None


def _density_sweep(
    g, grid, *, seed: int, reps: int, chunk_blocks: int
) -> list[dict]:
    import jax
    import jax.numpy as jnp

    from ..core import edgemap_reduce, edgemap_round_read_words

    x0 = jnp.arange(g.n, dtype=jnp.float32)
    dense_words = float(edgemap_round_read_words(g))
    modes = ["dense", "sparse"] + (["sparse_streamed"] if _has_streaming(g) else [])
    # measure at the chunk size the plan will actually run (the chunk sweep
    # picks it first) — timing sparse at a different chunk size skews the
    # crossover toward whichever side the mismatch slows down
    fns = {
        mode: jax.jit(
            lambda mask, x, mode=mode: edgemap_reduce(
                g, mask, x, monoid="min", mode=mode, chunk_blocks=chunk_blocks
            )
        )
        for mode in modes
    }
    rows = []
    for frac in grid:
        mask_np = _frontier_for_fraction(g, frac, seed)
        mask = jnp.asarray(mask_np)
        active = _active_block_fraction(g, mask_np)
        row = {
            "density": max(_measured_density(g, mask_np), 1e-6),
            "dense_words": dense_words,
            "sparse_words": dense_words * active,
        }
        for mode in modes:
            row[f"{mode}_us"] = _time_us(fns[mode], mask, x0, reps=reps)
        rows.append(row)
    rows.sort(key=lambda r: r["density"])
    return rows


def _chunk_sweep(g, grid, *, frac: float, seed: int, reps: int) -> list[dict]:
    import jax
    import jax.numpy as jnp

    from ..core import edgemap_reduce

    x0 = jnp.arange(g.n, dtype=jnp.float32)
    mask = jnp.asarray(_frontier_for_fraction(g, frac, seed))
    rows = []
    for cb in grid:
        fn = jax.jit(
            lambda mask, x, cb=cb: edgemap_reduce(
                g, mask, x, monoid="min", mode="sparse", chunk_blocks=cb
            )
        )
        rows.append({"chunk_blocks": int(cb), "us": _time_us(fn, mask, x0, reps=reps)})
    return rows


def _batch_sweep(g, grid, *, frac: float, seed: int, reps: int) -> list[dict]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..core import edgemap_reduce_batched

    rows = []
    for b in grid:
        masks = np.stack(
            [_frontier_for_fraction(g, frac, seed + i) for i in range(b)]
        )
        xb = jnp.broadcast_to(
            jnp.arange(g.n, dtype=jnp.float32)[None, :], (b, g.n)
        )
        fn = jax.jit(
            lambda masks, xb: edgemap_reduce_batched(
                g, masks, xb, monoid="min", mode="auto"
            )
        )
        us = _time_us(fn, jnp.asarray(masks), xb, reps=reps)
        rows.append({"B": int(b), "us_per_query": us / b})
    return rows


def _batched_density_sweep(
    g, grid, *, seed: int, reps: int, chunk_blocks: int, b: int = 8
) -> list[dict]:
    """Per-strategy batched (B-wide) round times across the density grid.

    The single-query crossover does NOT transfer to batched rounds: the
    batched dense body is one shared sweep + one segment reduce for all B
    lanes, while batched sparse vmaps B chunk loops — so dense wins batched
    at far lower densities than single-query.  Likewise the streamed union
    path runs ONE live-block loop shared by all lanes (wins when few
    blocks are live, loses once the union frontier covers most blocks).
    This sweep measures all of it at width ``b``: its dense/sparse sign
    flip becomes ``dense_frac_batched`` and its streamed/plain flip becomes
    ``batched_flavor_crossover``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..core import edgemap_reduce_batched

    modes = ["dense", "sparse"] + (
        ["sparse_streamed"] if _has_streaming(g) else []
    )
    rows = []
    for frac in grid:
        masks_np = np.stack(
            [_frontier_for_fraction(g, frac, seed + i) for i in range(b)]
        )
        masks = jnp.asarray(masks_np)
        xb = jnp.broadcast_to(
            jnp.arange(g.n, dtype=jnp.float32)[None, :], (b, g.n)
        )
        row = {
            "B": int(b),
            "density": max(
                float(np.mean([_measured_density(g, m) for m in masks_np])), 1e-6
            ),
        }
        for mode in modes:
            fn = jax.jit(
                lambda masks, xb, mode=mode: edgemap_reduce_batched(
                    g, masks, xb, monoid="min", mode=mode,
                    chunk_blocks=chunk_blocks,
                )
            )
            row[f"{mode}_us"] = _time_us(fn, masks, xb, reps=reps)
        rows.append(row)
    rows.sort(key=lambda r: r["density"])
    return rows


def _tile_sweep(g, grid, *, reps: int) -> list[dict]:
    """TB candidates for the streaming kernel (compressed backend only)."""
    import jax
    import jax.numpy as jnp

    from ..kernels.compressed_spmv import compressed_spmv_vertex

    x0 = jnp.arange(g.n, dtype=jnp.float32)
    rows = []
    for tb in grid:
        fn = jax.jit(lambda x, tb=tb: compressed_spmv_vertex(g, x, tile_blocks=tb))
        rows.append({"tile_blocks": int(tb), "us": _time_us(fn, x0, reps=reps)})
    return rows


def _knee(batch_sweep: list[dict], tol: float = 1.10) -> int:
    """Smallest B within ``tol`` of the best per-query amortization."""
    if not batch_sweep:
        return DEFAULT_MAX_BATCH
    best = min(r["us_per_query"] for r in batch_sweep)
    for r in sorted(batch_sweep, key=lambda r: r["B"]):
        if r["us_per_query"] <= tol * best:
            return int(r["B"])
    return int(batch_sweep[-1]["B"])


def _argmin(rows: list[dict], key: str, val: str, default: int) -> int:
    if not rows:
        return default
    return int(min(rows, key=lambda r: r[val])[key])


def _backend_entry(g, *, quick: bool, seed: int, reps: int, tile: bool) -> dict:
    density_grid = _DENSITY_GRID_QUICK if quick else _DENSITY_GRID
    chunk_grid = _CHUNK_GRID_QUICK if quick else _CHUNK_GRID
    batch_grid = _BATCH_GRID_QUICK if quick else _BATCH_GRID
    mid = density_grid[len(density_grid) // 2]

    # chunk size first: every later sweep times the sparse paths at the
    # chunk the plan will actually execute
    chunk_sweep = _chunk_sweep(g, chunk_grid, frac=mid, seed=seed, reps=reps)
    chunk_blocks = _argmin(chunk_sweep, "chunk_blocks", "us", DEFAULT_CHUNK_BLOCKS)

    sweep = _density_sweep(
        g, density_grid, seed=seed, reps=reps, chunk_blocks=chunk_blocks
    )
    crossover = crossover_from_sweep(sweep)
    batch_sweep = _batch_sweep(g, batch_grid, frac=mid, seed=seed, reps=reps)

    # Which sparse flavor auto's sparse branch should run: whichever
    # measured cheaper where sparse wins (the low-density half).
    auto_sparse = "sparse"
    if any("sparse_streamed_us" in r for r in sweep):
        lo = [r for r in sweep if r["density"] <= crossover] or sweep[:1]
        plain = sum(r["sparse_us"] for r in lo)
        streamed = sum(r.get("sparse_streamed_us", float("inf")) for r in lo)
        if streamed < plain:
            auto_sparse = "sparse_streamed"

    # Batched rounds get their OWN density sweep — neither the dense/sparse
    # crossover nor the sparse flavor transfers from the single-query
    # measurements (see _batched_density_sweep).
    batched_sweep = _batched_density_sweep(
        g, density_grid, seed=seed, reps=reps, chunk_blocks=chunk_blocks
    )
    batched_crossover = crossover_from_sweep(batched_sweep)
    flavor_crossover = flavor_crossover_from_sweep(batched_sweep)
    auto_sparse_batched = "sparse"
    if flavor_crossover is not None and flavor_crossover > 0:
        auto_sparse_batched = "sparse_streamed"

    entry = {
        "density_sweep": sweep,
        "crossover_density": crossover,
        "dense_frac": dense_frac_from_crossover(crossover),
        "chunk_sweep": chunk_sweep,
        "chunk_blocks": chunk_blocks,
        "batch_sweep": batch_sweep,
        "max_batch": _knee(batch_sweep),
        "auto_sparse": auto_sparse,
        "batched_density_sweep": batched_sweep,
        "batched_crossover_density": batched_crossover,
        "dense_frac_batched": dense_frac_from_crossover(batched_crossover),
        "auto_sparse_batched": auto_sparse_batched,
        "batched_flavor_crossover": flavor_crossover,
    }
    if tile and _has_streaming(g):
        tile_sweep = _tile_sweep(g, _TILE_GRID, reps=reps)
        entry["tile_sweep"] = tile_sweep
        entry["tile_blocks"] = _argmin(tile_sweep, "tile_blocks", "us", DEFAULT_TILE_BLOCKS)
    return entry


def _shard_sweep(g, *, reps: int) -> list[dict]:
    """Per-shard-count round times — only meaningful on multi-device hosts."""
    import jax
    import jax.numpy as jnp

    from ..core import edgemap_reduce, make_plan

    nd = jax.device_count()
    counts = [s for s in (1, 2, 4, 8) if s <= nd]
    if counts == [1]:
        return []
    x0 = jnp.arange(g.n, dtype=jnp.float32)
    mask = jnp.asarray(_frontier_for_fraction(g, 0.2, 0))
    rows = []
    for s in counts:
        plan = make_plan(g, mesh=s)
        gs = plan.prepare(g)
        fn = jax.jit(
            lambda mask, x: edgemap_reduce(gs, mask, x, monoid="min", plan=plan)
        )
        rows.append({"shards": int(s), "us": _time_us(fn, mask, x0, reps=reps)})
    return rows


def calibrate(
    *,
    n: int = 2048,
    m: int = 16384,
    quick: bool = False,
    seed: int = 0,
    reps: int = 3,
    block_size: int = 128,
    shards: bool = False,
) -> TuningTable:
    """Measure every knob on this host and return the TuningTable.

    ``quick`` shrinks the grids (3 density points, 2 chunk candidates,
    3 batch widths, no tile sweep) for the nightly-CI / cold-start path;
    full mode adds the TB tile sweep on the compressed backend.  ``shards``
    opts into the mesh sweep (needs ``XLA_FLAGS=--xla_force_host_platform_
    device_count=N`` on CPU hosts).  The calibration workload is the same
    R-MAT generator the benchmark tables use, symmetrized, weighted=False.
    """
    from ..core import compress
    from ..data.rmat import rmat_graph

    g = rmat_graph(n, m, seed=seed, block_size=block_size)
    gc = compress(g)
    tile = not quick
    data = {
        "schema_version": SCHEMA_VERSION,
        "created": None,  # stamped by the CLI (host wall clock)
        "quick": bool(quick),
        "host": host_fingerprint(),
        "graph": {"n": int(g.n), "m": int(g.m), "block_size": int(block_size)},
        "backends": {
            "csr": _backend_entry(g, quick=quick, seed=seed, reps=reps, tile=False),
            "compressed": _backend_entry(
                gc, quick=quick, seed=seed, reps=reps, tile=tile
            ),
        },
    }
    if shards:
        data["shard_sweep"] = _shard_sweep(g, reps=reps)
    return TuningTable.from_dict(data)
