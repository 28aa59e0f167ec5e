import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).

"""Multi-pod dry-run: lower + compile every (architecture × input-shape)
cell on the production meshes, and record memory/cost/collective statistics
for §Dry-run / §Roofline of EXPERIMENTS.md.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --mesh single --arch qwen2-1.5b
    PYTHONPATH=src python -m repro.launch.dryrun --mesh multi            # all
    PYTHONPATH=src python -m repro.launch.dryrun --list

Results are cached as JSON under results/dryrun/ (one file per cell×mesh);
re-runs skip completed cells, so the sweep is resumable.
"""
import argparse
import json
import re
import time
import traceback

import jax

from ..compat import use_mesh
from ..configs import all_cells
from ..distributed.shardings import axis_rules
from .mesh import make_production_mesh
from .steps import build_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "results", "dryrun")

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def cost_dict(compiled) -> dict:
    """``Compiled.cost_analysis()``, or ``{}`` where the backend has none."""
    return compiled.cost_analysis() or {}


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
        break  # first shape on the line = result shape
    return total


def collective_bytes_from_hlo(hlo_text: str, scan_trip_hint: int = 1) -> dict:
    """Sum result-shape bytes of every collective op in the HLO.

    Ops inside while-loop bodies (layer scans) are multiplied by
    ``scan_trip_hint`` — XLA prints the body once but executes it per layer.
    """
    per_op = {c: 0 for c in _COLLECTIVES}
    in_while = False
    depth = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        if s.startswith(("%while", "while_body", "%body", "body")) and s.endswith("{"):
            in_while = True
            depth = 0
        if in_while:
            depth += s.count("{") - s.count("}")
            if depth <= 0 and "}" in s:
                in_while = False
        mult = scan_trip_hint if in_while else 1
        for c in _COLLECTIVES:
            if f" {c}(" in s or f"= {c}" in s or re.search(rf"\b{c}(\.\d+)?\(", s):
                per_op[c] += _shape_bytes(s) * mult
                break
    per_op["total"] = sum(per_op[c] for c in _COLLECTIVES)
    return per_op


def scan_trips_for(cell) -> int:
    cfg = cell.model_cfg
    return getattr(cfg, "n_layers", None) or getattr(cfg, "n_blocks", 1) or 1


_DEF_RE = re.compile(r"^\s*(%[\w.\-]+)\s*=\s*(\w+)\[([\d,]*)\]")
_DOT_RE = re.compile(r"^\s*(%[\w.\-]+)\s*=\s*(\w+)\[([\d,]*)\][^=]*\bdot\(([^)]*)\)")
_LHS_C_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")


def dot_flops_from_hlo(hlo_text: str) -> float:
    """Sum 2·|result|·contract_size over every dot op in the module.

    XLA:CPU's aggregate cost_analysis drops some SPMD-partitioned batched
    dots (observed: the attention einsums vanish from the total); parsing
    the dots directly is exact on fully-unrolled modules (the costing
    variants contain no while loops, so no trip-count ambiguity).
    """
    shape_of = {}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if m:
            shape_of[m.group(1)] = tuple(
                int(d) for d in m.group(3).split(",") if d
            )
    flops = 0.0
    for line in hlo_text.splitlines():
        m = _DOT_RE.match(line)
        if not m:
            continue
        result_dims = tuple(int(d) for d in m.group(3).split(",") if d)
        result_elems = 1.0
        for d in result_dims:
            result_elems *= d
        ops = [o.strip().split(" ")[-1] for o in m.group(4).split(",")]
        lhs = shape_of.get(ops[0], ()) if ops else ()
        mc = _LHS_C_RE.search(line)
        cdims = [int(d) for d in mc.group(1).split(",") if d] if mc else []
        contract = 1.0
        for d in cdims:
            if d < len(lhs):
                contract *= lhs[d]
        flops += 2.0 * result_elems * contract
    return flops


def _compile_cost_variant(cell, mesh, n_layers: int):
    """Compile a small FULLY-UNROLLED variant of an LM cell and return
    (per-device flops, bytes).  Two corrections vs the scanned main compile:
    scan bodies are counted once by cost_analysis (fixed by unrolling +
    F(L+1)−F(L) extrapolation), and SPMD-partitioned batched dots are
    dropped from the aggregate (fixed by dot_flops_from_hlo — we take the
    max of XLA's aggregate and the parsed dot flops)."""
    import dataclasses

    # single-block attention so the kv scan doesn't hide FLOPs; decode cells
    # already use kv_block == cache length (the cache is sized from it), and
    # causal-skip variants must keep their blocking (unroll_kv makes the kv
    # loop visible either way)
    kv_block = (
        cell.model_cfg.kv_block
        if (cell.kind == "decode" or getattr(cell.model_cfg, "attn_causal_skip", False))
        else max(cell.model_cfg.kv_block, 1 << 30)
    )
    cfg = dataclasses.replace(
        cell.model_cfg,
        n_layers=n_layers,
        unroll=True,
        kv_block=kv_block,
    )
    cc = dataclasses.replace(cell, model_cfg=cfg)
    fn, specs, shardings, out_shardings = build_step(cc, mesh)
    with use_mesh(mesh), axis_rules(cell.rules, mesh):
        compiled = jax.jit(
            fn, in_shardings=shardings, out_shardings=out_shardings
        ).lower(*specs).compile()
    cost = cost_dict(compiled)
    xla_flops = float(cost.get("flops", 0))
    parsed = dot_flops_from_hlo(compiled.as_text())
    return max(xla_flops, parsed), float(cost.get("bytes accessed", 0))


def corrected_lm_cost(cell, mesh):
    """Extrapolate total per-device flops/bytes: F(Lmin) + (L−Lmin)·ΔF."""
    cfg = cell.model_cfg
    lmin = (cfg.first_dense_layers if cfg.moe else 0) + 1
    f1, b1 = _compile_cost_variant(cell, mesh, lmin)
    f2, b2 = _compile_cost_variant(cell, mesh, lmin + 1)
    L = cfg.n_layers
    flops = f1 + (L - lmin) * (f2 - f1)
    byts = b1 + (L - lmin) * (b2 - b1)
    return flops, byts, {"f_lmin": f1, "f_lmin1": f2, "lmin": lmin}


def run_cell(cell, mesh, mesh_name: str, out_dir: str):
    key = f"{cell.arch}__{cell.shape}__{mesh_name}".replace("/", "_")
    out_path = os.path.join(out_dir, key + ".json")
    if os.path.exists(out_path):
        with open(out_path) as fh:
            return json.load(fh)
    t0 = time.time()
    rec = {
        "arch": cell.arch, "shape": cell.shape, "mesh": mesh_name,
        "kind": cell.kind, "family": cell.family, "notes": cell.notes,
        "model_flops": cell.model_flops,
    }
    try:
        fn, specs, shardings, out_shardings = build_step(cell, mesh)
        with use_mesh(mesh), axis_rules(cell.rules, mesh):
            jitted = jax.jit(fn, in_shardings=shardings, out_shardings=out_shardings)
            lowered = jitted.lower(*specs)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = cost_dict(compiled)
        hlo = compiled.as_text()
        coll = collective_bytes_from_hlo(hlo, scan_trips_for(cell))
        flops_raw = float(cost.get("flops", -1)) if cost else -1
        bytes_raw = float(cost.get("bytes accessed", -1)) if cost else -1
        if cell.family == "lm":
            flops_dev, bytes_dev, cost_dbg = corrected_lm_cost(cell, mesh)
        else:
            # GNN/recsys models are python-loop (no scans): the main module
            # is exact; still recover SPMD-dropped batched dots by parsing
            flops_dev = max(flops_raw, dot_flops_from_hlo(hlo))
            bytes_dev, cost_dbg = bytes_raw, {}
        rec.update(
            ok=True,
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            n_devices=int(mesh.devices.size),
            flops_raw_per_device=flops_raw,
            flops_per_device=flops_dev,
            bytes_per_device=bytes_dev,
            bytes_raw_per_device=bytes_raw,
            cost_debug=cost_dbg,
            collective_bytes=coll,
            memory={
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
            },
        )
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec.update(ok=False, error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    os.makedirs(out_dir, exist_ok=True)
    with open(out_path, "w") as fh:
        json.dump(rec, fh, indent=1)
    status = "OK " if rec.get("ok") else "FAIL"
    print(f"[{status}] {key}  ({time.time() - t0:.1f}s)", flush=True)
    return rec


def run_graph_engine(mesh, mesh_name: str, out_dir: str, *, rules_name: str = "baseline"):
    """Dry-run the Sage engine itself on the production mesh: one
    edge-partitioned PageRank round + one BFS/label-prop round over a
    production-scale RMAT stand-in (n=2^20 vertices, NB=2^18 blocks of 128).
    """
    import jax.numpy as jnp

    from ..core.plan import sharded_graph_spec
    from ..distributed.engine import (
        distributed_frontier_min,
        distributed_pagerank_step,
    )

    n, NB, FB = 1 << 20, 1 << 18, 128
    S = jax.ShapeDtypeStruct
    gs = sharded_graph_spec(n, NB, FB, int(mesh.devices.size))
    x = S((n,), jnp.float32)
    xi = S((n,), jnp.int32)
    fr = S((n,), jnp.bool_)

    for name, build, specs in [
        ("pagerank_round", lambda: distributed_pagerank_step(mesh, n=n), (gs, x, x)),
        ("frontier_min", lambda: distributed_frontier_min(mesh, n=n), (gs, xi, fr)),
    ]:
        key = f"sage-graph__{name}_{rules_name}__{mesh_name}"
        out_path = os.path.join(out_dir, key + ".json")
        if os.path.exists(out_path):
            continue
        t0 = time.time()
        rec = {"arch": "sage-graph", "shape": f"{name}_{rules_name}",
               "mesh": mesh_name, "kind": "graph", "family": "graph",
               "notes": f"n={n} NB={NB} FB={FB}",
               "model_flops": 2.0 * NB * FB}
        try:
            fn = build()
            with use_mesh(mesh):
                compiled = jax.jit(fn).lower(*specs).compile()
            cost = cost_dict(compiled)
            mem = compiled.memory_analysis()
            coll = collective_bytes_from_hlo(compiled.as_text(), 1)
            rec.update(
                ok=True,
                n_devices=int(mesh.devices.size),
                flops_per_device=float(cost.get("flops", -1)),
                flops_raw_per_device=float(cost.get("flops", -1)),
                bytes_per_device=float(cost.get("bytes accessed", -1)),
                bytes_raw_per_device=float(cost.get("bytes accessed", -1)),
                cost_debug={},
                collective_bytes=coll,
                memory={
                    "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                    "output_bytes": getattr(mem, "output_size_in_bytes", None),
                    "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                    "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
                },
            )
        except Exception as e:  # noqa: BLE001
            rec.update(ok=False, error=f"{type(e).__name__}: {e}")
        os.makedirs(out_dir, exist_ok=True)
        with open(out_path, "w") as fh:
            json.dump(rec, fh, indent=1)
        print(f"[{'OK ' if rec.get('ok') else 'FAIL'}] {key} ({time.time()-t0:.1f}s)",
              flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--graph-engine", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--out", default=RESULTS_DIR)
    args = ap.parse_args()

    cells = all_cells()
    if args.list:
        for (a, s) in sorted(cells):
            print(a, s, cells[(a, s)].kind)
        return

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single_pod_16x16", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi_pod_2x16x16", make_production_mesh(multi_pod=True)))

    if args.graph_engine:
        for mesh_name, mesh in meshes:
            run_graph_engine(mesh, mesh_name, args.out)
        return

    n_ok = n_fail = 0
    for mesh_name, mesh in meshes:
        for (arch, shape), cell in sorted(cells.items()):
            if args.arch and arch != args.arch:
                continue
            if args.shape and shape != args.shape:
                continue
            rec = run_cell(cell, mesh, mesh_name, args.out)
            n_ok += bool(rec.get("ok"))
            n_fail += not rec.get("ok")
    print(f"done: {n_ok} ok, {n_fail} failed")


if __name__ == "__main__":
    main()
