"""One run of one cell, as ``BENCHMARK.json`` names it.

Every piece is found by name: the cell names a configuration (its file is
given in ``configs``) and a traffic file ``bench/traffic/<traffic>.json``,
whose ``job`` names a job kind ``bench/jobs/<job>.py``; each metric is read
by ``bench/metrics/<metric>.py``.  Adding a configuration, a cell or a
metric is adding files and entries; no file here changes.

A run: set-up (generate the graph on the device from the configuration's
seed, the program's ingest, ``make_plan``, compile the cell's one program,
one warm job), then a closed loop with one job in flight that starts jobs
until ``--seconds`` have passed and finishes the job in flight, then the
check against the plain reference of every job's answer, the warm job's
and those of the jobs the kind runs untimed after the window.  With
``--trace 1`` the window runs under the profiler and the per-layer metrics
are read from the trace.  The last line of standard output is the result.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import itertools
import json
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str, e2e_names: set | None) -> bool:
    """Does ``metric`` report in ``cell``?  Its ``workloads`` say so where
    given; otherwise an end-to-end metric reports everywhere and a per-layer
    metric wherever the end-to-end metric it moves reports."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


class Cell:
    """Everything a run of one cell reads, found through ``BENCHMARK.json``."""

    def __init__(self, root: str, workload: str):
        self.root = root
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            spec = json.load(f)
        cells = {w["name"]: w for w in spec["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
        self.cell = cells[workload]
        cfg = {c["name"]: c for c in spec["configs"]}[self.cell["config"]]
        with open(os.path.join(root, cfg["file"])) as f:
            self.config = json.load(f)
        bench = os.path.join(root, "bench")
        with open(os.path.join(bench, "traffic", self.cell["traffic"] + ".json")) as f:
            self.traffic = json.load(f)
        self.job = load_module(os.path.join(bench, "jobs", self.traffic["job"] + ".py"),
                               f"bench_job_{self.traffic['job']}")
        unknown = set(self.traffic) - {"job", "about"} - set(self.job.KEYS)
        if unknown:
            raise ValueError(f"traffic keys that job {self.traffic['job']!r} does not read: "
                             f"{sorted(unknown)}")
        name = self.cell["name"]
        self.end_to_end = [m for m in spec["end_to_end"] if _applies(m, name, None)]
        e2e = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in spec["per_layer"] if _applies(m, name, e2e)]
        with open(os.path.join(bench, "peaks.json")) as f:
            self.peaks_table = json.load(f)
        self.bench = bench

    def reader(self, metric: str):
        return load_module(os.path.join(self.bench, "metrics", metric + ".py"),
                           "bench_metric_" + metric.replace(".", "_"))

    def peaks(self, device_kind: str) -> dict:
        rows = self.peaks_table["devices"]
        if device_kind not in rows:
            raise KeyError(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
        return rows[device_kind]


def devices(chips: int, allow_cpu: bool = False):
    """The cell's chips, or ``NoChip``: a run never falls back to the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu" and not allow_cpu:
        raise NoChip("JAX found no accelerator (platform cpu)")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


class CompileCounter:
    """Counts executables compiled or loaded from the cache, and cache hits."""

    def __init__(self):
        import jax
        from jax._src import dispatch

        self.event = dispatch.BACKEND_COMPILE_EVENT
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, _secs, **_kw):
        if event == self.event:
            self.compiles += 1

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


def set_env(root: str) -> None:
    """Process settings that must precede JAX's import: its persistent
    compilation cache at a fixed path inside the checkout, every program
    kept, so that only a cell's first run in a checkout compiles, whatever
    cache the machine names; the TPU runtime's logs inside the checkout."""
    cache = os.path.join(root, ".bench_cache")
    # JAX writes no entry into a directory that does not exist
    os.makedirs(os.path.join(cache, "jax"), exist_ok=True)
    os.makedirs(os.path.join(cache, "tpu_logs"), exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(cache, "jax")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    # no eviction: with it on, JAX refuses to write into a directory holding
    # an entry written without it (an entry with no access-time file)
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(cache, "tpu_logs"))


def say(tag: str, **kv) -> None:
    print(f"[{tag}] " + " ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


def run(cell: Cell, *, seed: int, seconds: float, trace: bool, t_start: float,
        allow_cpu: bool = False) -> dict:
    """One run; returns the result line as a dict."""
    import jax

    from repro.core import make_plan

    devs = devices(int(cell.cell["chips"]), allow_cpu)
    counter = CompileCounter()
    dev = devs[0]
    say("device", platform=dev.platform, kind=repr(dev.device_kind), count=len(devs))

    times: dict = {}
    from bench import graph

    g, info = graph.build(cell.config, times)
    say("graph", n=info.n, m_undirected=info.m_undirected, directed_slots=info.m_directed,
        blocks=info.blocks, exceptions=info.exceptions, layout=cell.config["layout"])

    job = cell.job
    t = time.perf_counter()
    plan = make_plan(g)
    fn, to_args = job.program(plan, cell.traffic)
    times["plan_s"] = time.perf_counter() - t
    warm, jobs, after = job.draw(info, cell.traffic, seed)
    stream = itertools.cycle(jobs)

    c0, h0 = counter.compiles, counter.cache_hits
    t = time.perf_counter()
    compiled = jax.jit(fn).lower(g, *to_args(*warm)).compile()
    times["compile_s"] = time.perf_counter() - t
    t = time.perf_counter()
    warm_out = job.keep(jax.block_until_ready(compiled(g, *to_args(*warm))))
    times["warm_s"] = time.perf_counter() - t
    hlo_text = compiled.as_text() if trace else ""
    times["setup_s"] = time.perf_counter() - t_start
    say("setup", **{k: repr(v) for k, v in times.items()},
        programs=counter.compiles - c0, cache_hits=counter.cache_hits - h0,
        plan=repr(plan.describe()))

    trace_dir = os.path.join(cell.root, ".bench_cache", "trace", cell.cell["name"])
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    c_before = counter.compiles
    kept, spans = window(compiled, g, to_args, stream, job, seconds)
    if trace:
        jax.profiler.stop_trace()
    in_window = counter.compiles - c_before
    counter.close()
    window_s = spans[-1][1] - spans[0][0]
    say("window", jobs=len(spans), seconds=repr(window_s), compiles_in_window=in_window)
    say("jobs", seconds=json.dumps([e - s for s, e in spans]),
        args=json.dumps([list(a) for a, _ in kept]))

    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    say("memory", peak_bytes_in_use=peak, bytes_limit=stats.get("bytes_limit"))

    # untimed, the same program: more answers for the check
    kept += [(warm, warm_out)] + [(a, job.keep(compiled(g, *to_args(*a)))) for a in after]
    # the program's state goes before the reference runs on the host
    answers = [(args, jax.device_get(out)) for args, out in kept]
    del kept, warm_out, compiled, g
    gc.collect()

    timed = answers[:len(spans)]
    record = {
        "config": cell.config, "traffic": cell.traffic, "setup": times, "graph": info,
        "window": {"jobs": len(spans), "seconds": window_s,
                   "work": job.work(info) * len(spans),
                   "rounds": sum(job.rounds(out) for _, out in timed)},
        "peaks": cell.peaks(dev.device_kind) if dev.platform != "cpu" else None,
        "trace": None,
    }
    names = cell.per_layer if trace else cell.end_to_end
    readers = {m["name"]: cell.reader(m["name"]) for m in names}
    if trace:
        from bench import tracing

        scopes = sorted({s for r in readers.values() for s in getattr(r, "SCOPES", ())})
        host, device_ops = tracing.load(tracing.find_xplane(trace_dir))
        record["trace"] = tr = tracing.reduce(host, device_ops, scopes=scopes,
                                              hlo_map=tracing.hlo_op_names(hlo_text))
        say("trace", window_s=repr(tr["window_s"]), busy_s=repr(tr["busy_s"]),
            chips=tr["chips"], **{k: repr(v) for k, v in tr["scope_s"].items()})

    checks, limits, failed = check(job, info, answers, cell.traffic, spans)
    say("checked", jobs=len(answers), timed=len(spans),
        args=json.dumps([list(a) for a, _ in answers[len(spans):]]))
    metrics = {}
    for m in names:
        value = readers[m["name"]].read(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    correct = (failed == 0 and len(answers) > 0 and in_window == 0
               and all(checks[k] <= limits[k] for k in limits))
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs),
              "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": len(spans), "failed": int(failed),
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = record["trace"]["busy_s"]
        device["window_s"] = record["trace"]["window_s"]
        result["breakdown"] = {"device_ops": record["trace"]["device_ops"],
                               "idle_gaps": record["trace"]["idle_gaps"]}
    result["checks"] = {k: {"value": checks[k], "limit": limits[k]} for k in limits}
    return result


def window(compiled, g, to_args, stream, job, seconds: float):
    """The closed loop: one job in flight, new jobs started until
    ``seconds`` have passed, the job in flight finished.  Returns each job's
    (args, kept output on the device) and its (start, end) host times."""
    import jax

    kept, spans = [], []
    t_first = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            args = next(stream)
            ts = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.job"):
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    out = compiled(g, *to_args(*args))
                with jax.profiler.TraceAnnotation("bench.wait"):
                    out = jax.block_until_ready(out)
            te = time.perf_counter()
            kept.append((args, job.keep(out)))
            spans.append((ts, te))
            if te - t_first >= seconds:
                return kept, spans


def check(job, info, answers, traffic: dict, spans):
    """Every job's answer against the plain reference, built from the
    generator's tuples: the window's jobs first, one per span, then those
    run untimed.  Returns ({number: worst over all jobs}, {number: limit},
    the window's jobs that failed)."""
    from bench import reference

    t = time.perf_counter()
    ref = reference.RefGraph(info.n, info.src, info.dst)
    per_job = job.check(ref, answers, traffic)
    limits = dict(job.LIMITS)
    checks = {name: max(r[name] for r in per_job) for name in limits}
    failed = sum(any(r[name] > lim for name, lim in limits.items())
                 for r in per_job[:len(spans)])
    # the ingest's edge count against the reference's
    checks["graph_m_gap"] = abs(info.m_directed - ref.m_directed)
    limits["graph_m_gap"] = 0
    if "edges_reached" in per_job[0]:
        teps = [r["edges_reached"] / (e - s) for r, (s, e) in zip(per_job, spans)]
        say("teps", harmonic_mean=repr(len(teps) / sum(1.0 / x for x in teps)),
            min=repr(min(teps)), max=repr(max(teps)))
    say("reference", seconds=repr(time.perf_counter() - t))
    return checks, limits, failed


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True, help="a cell of BENCHMARK.json")
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True, help="length of the window")
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None, *, root: str | None = None, t_start: float | None = None,
         allow_cpu: bool = False) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    root = root or os.path.dirname(BENCH)
    sys.path.insert(0, os.path.join(root, "src"))
    cell = Cell(root, args.workload)
    try:
        result = run(cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
                     t_start=t_start, allow_cpu=allow_cpu)
    except NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} value={c['value']!r} limit={c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
