"""Reduce a profiler trace of the window to device busy time, scope time
and the idle gaps, named by what the host was doing.

The window is the host span ``bench.window`` that the harness writes
around its jobs (``jax.profiler.TraceAnnotation``).  Device operations are
the events of the ``XLA Ops`` line of each ``/device:`` plane; busy time is
the union of their intervals inside the window, per chip, averaged over the
chips.  An operation belongs to a named scope (``sage.round``) where the
scope is a component of its op name: the ``op_name`` metadata of the
instruction of that name in the compiled program's HLO text.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW = "bench.window"
OP_LINES = ("XLA Ops",)
# back-to-back ops leave gaps of a few ns in the trace: not idle time worth naming
MIN_GAP_NS = 1000
_HLO_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name=\"([^\"]*)\"")


def hlo_op_names(hlo_text: str) -> dict:
    """{instruction name: op_name metadata} of an HLO module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_LINE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals):
    """Merged, sorted list of [start, end] intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def load(path: str):
    """(host spans, device ops per plane) of an ``.xplane.pb`` file.

    Host spans are (name, start_ns, end_ns) of every host event; device
    ops are (name, start_ns, end_ns) per device plane name."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host, devices = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                if line.name not in OP_LINES:
                    continue
                for e in line.events:
                    ops.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return host, devices


_NAME = re.compile(r"^%?([\w.\-]+)(?: = |$)")


def short_name(name: str) -> str:
    """``fusion.29`` of an event named by its whole HLO instruction, as TPU
    traces name them (``%fusion.29 = f32[...] fusion(...), ...``)."""
    m = _NAME.match(name)
    return m.group(1) if m else name


def scope_of(name: str, hlo_map: dict) -> str:
    """The op name path of an event: the ``op_name`` metadata of the HLO
    instruction it names (TPU op events carry no such stat of their own)."""
    return hlo_map.get(short_name(name), "")


def _self_times(ops):
    """{index: exclusive ns} of possibly nested op intervals on one line:
    each op's duration less that of the ops directly inside it."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    self_ns = {i: ops[i][1] - ops[i][0] for i in order}
    stack = []
    for i in order:
        s, e = ops[i]
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack and ops[stack[-1]][1] >= e:
            self_ns[stack[-1]] -= e - s
        stack.append(i)
    return self_ns


def reduce(host, devices, *, scopes=(), hlo_map=None, top=10) -> dict:
    """Numbers of the window: its length, device busy time (mean over the
    chips), device time per scope (mean over chips), and the breakdown:
    the device ops that took most time and the longest idle gaps."""
    hlo_map = hlo_map or {}
    wins = [(s, e) for n, s, e in host if n == WINDOW]
    if not wins:
        raise ValueError(f"no {WINDOW} span in the trace")
    w0, w1 = wins[0]
    spans = [(n, s, e) for n, s, e in host
             if n.startswith("bench.") and n != WINDOW and e > w0 and s < w1]
    busy_ns, scope_ns = 0.0, {sc: 0.0 for sc in scopes}
    op_ns, gaps = {}, []
    chips = max(len(devices), 1)
    for ops in devices.values():
        inside, names, in_scope = [], [], {sc: [] for sc in scopes}
        for name, s, e in ops:
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            inside.append((s, e))
            names.append(short_name(name))
            parts = scope_of(name, hlo_map).split("/")
            for sc in scopes:
                if sc in parts:
                    in_scope[sc].append((s, e))
        # ops nest (a while or a conditional holds the ops it runs): time
        # under a scope is the union of its ops' intervals, and an op's own
        # time excludes the ops inside it
        for sc, ivs in in_scope.items():
            scope_ns[sc] += sum(e - s for s, e in _union(ivs))
        for i, t in _self_times(inside).items():
            op_ns[names[i]] = op_ns.get(names[i], 0.0) + t
        merged = _union(inside)
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge - gs >= MIN_GAP_NS:
                gaps.append((_host_activity(spans, (gs + ge) / 2), (ge - gs) / 1e9))
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
    # name each op with the scope path it came from
    top_ops = [(f"{n} {hlo_map[n]}" if n in hlo_map else n, t) for n, t in top_ops]
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / chips / 1e9,
        "scope_s": {sc: v / chips / 1e9 for sc, v in scope_ns.items()},
        "device_ops": [[n, v / chips / 1e9] for n, v in top_ops],
        "idle_gaps": [list(g) for g in sorted(gaps, key=lambda g: -g[1])[:top]],
        "chips": len(devices),
    }


def _host_activity(spans, t) -> str:
    """Name of the innermost ``bench.*`` host span covering time ``t``."""
    best = None
    for n, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, n)
    return best[2] if best else "host:outside-bench-spans"
