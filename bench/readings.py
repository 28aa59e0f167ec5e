#!/usr/bin/env python3
"""Readings that the correctness limits are set from, for one cell.

    python3 bench/readings.py --workload kron21.bfs --seeds 1,2,...,12 \
        --jobs-per-seed 3 --control-seeds 3 --out readings.json

One process builds the cell's graph and compiles its program once, then
for each seed runs the first ``--jobs-per-seed`` jobs of that seed's window
and the jobs its run checks after the window, on the chip, and compares
them with the plain reference: the lower reading of each
number is the largest that sound runs give.  For the first
``--control-seeds`` seeds it also puts the job kind's control (the
reference, broken as a later change would be tempted to) in the program's
place on the same jobs: the upper reading is the smallest that the control
gives.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True, help="comma-separated seeds")
    p.add_argument("--jobs-per-seed", type=int, default=1)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)

    from bench import harness

    harness.set_env(ROOT)
    import jax

    from bench import graph, reference
    from repro.core import make_plan

    cell = harness.Cell(ROOT, args.workload)
    try:
        devs = harness.devices(int(cell.cell["chips"]))
    except harness.NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    g, info = graph.build(cell.config, {})
    job = cell.job
    fn, to_args = job.program(make_plan(g), cell.traffic)
    seeds = [int(s) for s in args.seeds.split(",")]
    first = job.draw(info, cell.traffic, seeds[0])[0]
    compiled = jax.jit(fn).lower(g, *to_args(*first)).compile()

    jobs, owner = [], []
    for seed in seeds:
        # the first jobs of a run's window, then those it checks untimed
        _, stream, after = job.draw(info, cell.traffic, seed)
        for a in [stream[i % len(stream)] for i in range(args.jobs_per_seed)] + after:
            t = time.perf_counter()
            out = jax.device_get(job.keep(compiled(g, *to_args(*a))))
            jobs.append((a, out))
            owner.append(seed)
            print(f"[job] seed={seed} args={list(a)} seconds={time.perf_counter() - t!r}",
                  flush=True)
    peak = (devs[0].memory_stats() or {}).get("peak_bytes_in_use")
    del compiled, g
    ref = reference.RefGraph(info.n, info.src, info.dst)
    rows = job.check(ref, jobs, cell.traffic)
    control_seeds = seeds[: args.control_seeds]
    cjobs = [(a, job.control(ref, a, cell.traffic))
             for (a, _), s in zip(jobs, owner) if s in control_seeds]
    crows = job.check(ref, cjobs, cell.traffic)
    cowner = [s for s in owner if s in control_seeds]

    names = list(job.LIMITS)
    per_seed = []
    for seed in seeds:
        prog = [r for r, s in zip(rows, owner) if s == seed]
        entry = {"seed": seed, "program": {n: max(r[n] for r in prog) for n in names}}
        ctrl = [r for r, s in zip(crows, cowner) if s == seed]
        if ctrl:
            entry["control"] = {n: max(r[n] for r in ctrl) for n in names}
        per_seed.append(entry)
        print("[seed] " + json.dumps(entry), flush=True)
    summary = {
        "workload": args.workload,
        "device": {"kind": devs[0].device_kind, "memory_peak_bytes": peak},
        "jobs": len(jobs),
        "lower": {n: max(e["program"][n] for e in per_seed) for n in names},
        "upper": {n: min(e["control"][n] for e in per_seed if "control" in e)
                  for n in names},
        "limits": job.LIMITS,
        "seeds": per_seed,
    }
    print(json.dumps(summary), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
