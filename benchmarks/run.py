"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines.

  fig1_suite    — Fig. 1 / Fig. 6: the 18-algorithm suite + PSAM work model
  table4_filter — Table 4: filter block size F_B ↔ triangle-count work
  table4_filter_planned — filtered edgeMap via the kernel edge_active
                  operand (raw + compressed) and a 4-shard mesh
  table5_edgemap— Table 5: edgeMap variant ↔ peak intermediate memory
  table_compression — §5.1.3: compression ratio + compressed edgeMap throughput
  table_distributed — planner: per-shard PageRank throughput, compressed vs raw
  table_serving — QueryEngine: queries/sec vs batch size B, both backends,
                  + PSAM edge-read amortization at B=8
  table_latency — ServingService: p50/p99 latency over Poisson + bursty
                  arrival traces, qps-vs-SLO curve, saturated-B8 vs engine
  table_streaming — delta overlay: edit-plus-query trace replay, per-edit
                  and compaction costs, PSAM amortization vs recompress-
                  per-edit (in-bench asserted >= 10x at batch 1000)
  table_autotune— tuning: strategy="auto" vs every fixed strategy across a
                  frontier-density sweep (in-bench asserted) + BFS/wBFS/
                  PageRank replays under an in-run calibrated table
  fig_layout    — §5.2: pod-replicated layout ↔ collective bytes
  kernels_micro — Pallas kernels vs jnp oracles
  roofline      — §Roofline terms from the dry-run artifacts (if present)
"""
from __future__ import annotations

import argparse
import sys

from repro.runtime import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma list of benches")
    ap.add_argument("--full", action="store_true", help="paper-scale sizes")
    ap.add_argument(
        "--profile",
        default=None,
        metavar="DIR",
        help="capture a jax.profiler trace of the selected benches into DIR "
        "(one TraceAnnotation span per bench; the traced rounds carry the "
        "sage.round / sage.shard_combine named scopes)",
    )
    args = ap.parse_args()
    enable_compile_cache()

    from . import (fig1_suite, fig7_dram_nvram, fig_layout, kernels_micro,
                   table4_filter, table5_edgemap, table_autotune,
                   table_compression, table_distributed, table_latency,
                   table_serving, table_streaming)

    benches = {
        "fig1_suite": lambda: fig1_suite.run(
            n=4096 if args.full else 1024, m=32768 if args.full else 8192
        ),
        "table4_filter": lambda: table4_filter.run(
            n=2048 if args.full else 512, m=16384 if args.full else 4096
        ),
        # planner-native filter columns: Pallas edge_active operand (raw +
        # compressed) and the 4-shard sharded-filter path
        "table4_filter_planned": lambda: table4_filter.run_planned(
            n=2048 if args.full else 512, m=16384 if args.full else 4096
        ),
        "table5_edgemap": lambda: table5_edgemap.run(
            n=4096 if args.full else 1024, m=65536 if args.full else 8192
        ),
        "table_compression": lambda: table_compression.run(
            n=4096 if args.full else 1024, m=65536 if args.full else 8192
        ),
        # --full is RMAT-20: 2^20 vertices, the paper-scale stand-in
        "table_distributed": lambda: table_distributed.run(
            n=(1 << 20) if args.full else 4096,
            m=(1 << 22) if args.full else 16384,
        ),
        # queries/sec vs batch size through the QueryEngine (both backends)
        "table_serving": lambda: table_serving.run(
            n=4096 if args.full else 1024, m=32768 if args.full else 8192
        ),
        # deadline-driven drain loop: latency percentiles over replayed
        # arrival traces + the saturated-B8 qps parity with the engine
        "table_latency": lambda: table_latency.run(
            n=4096 if args.full else 1024, m=32768 if args.full else 8192
        ),
        # mutable serving: delta-overlay edit replay + compaction
        # amortization vs recompress-per-edit (PSAM words, asserted)
        "table_streaming": lambda: table_streaming.run(
            n=4096 if args.full else 1024, m=32768 if args.full else 8192
        ),
        # auto-vs-fixed strategy spread with an in-run calibrated table;
        # always the calibration-default workload — the in-bench tolerance
        # asserts were validated at this size, smaller graphs compress the
        # strategy spread below the asserted margins
        "table_autotune": lambda: table_autotune.run(
            n=2048, m=16384, reps=3 if args.full else 2
        ),
        "kernels_micro": kernels_micro.run,
        "fig_layout": fig_layout.run,
        "fig7_dram_nvram": fig7_dram_nvram.run,
    }
    from . import roofline

    if roofline.load_records():
        benches["roofline"] = roofline.run

    only = set(args.only.split(",")) if args.only else None
    import contextlib

    if args.profile:
        # the shared tracing session (repro.obs) — same capture the serving
        # tier uses, so bench traces and service traces read identically
        from repro.obs import annotate, trace_session

        session = trace_session(args.profile)
    else:
        annotate = None
        session = contextlib.nullcontext()
    failed = []
    with session:
        print("name,us_per_call,derived")
        for name, fn in benches.items():
            if only and name not in only:
                continue
            try:
                if annotate is not None:
                    with annotate(f"bench.{name}"):
                        rows = fn()
                else:
                    rows = fn()
                for r in rows:
                    print(
                        f"{r['name']},{r['us_per_call']:.0f},{r['derived']}",
                        flush=True,
                    )
            except Exception as e:  # noqa: BLE001
                # keep going so one run reports every bench, then fail
                print(f"{name},-1,ERROR: {type(e).__name__}: {e}", flush=True)
                failed.append(name)
    if failed:
        print(f"benchmarks failed: {','.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
