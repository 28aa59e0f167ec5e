"""Tracing — named device scopes, host spans, and a profiler session.

Device work is named with ``jax.named_scope``: the name lands in the HLO
``op_name`` metadata of every instruction traced under it, so a profiler
trace of the chip attributes each op's time to the scopes around it, at no
run-time cost.  The engine's scopes:

* ``sage.round`` — one round of the sequential round loop (``round_loop``),
  exactly the edgeMap call;
* ``sage.round.sweep`` — the local sweep of a round on the pipelined sharded
  path only (the combine of that path sits outside it);
* ``sage.sweep.dense`` / ``sage.sweep.sparse`` — the dense (pull) sweep and
  the chunked (sparse) sweep, single and batched (``core/edgemap.py``);
* ``sage.decode`` — the compressed backend's target decode: the whole-graph
  decode the dense sweep reads and the per-tile decode of the sparse sweep
  (``core/compressed.py``, the streamed tile of ``core/edgemap.py``);
* ``sage.shard_combine`` — the cross-shard monoid combine.

A scope traced inside ``jax.vmap`` reads ``vmap(<name>)`` in the op name;
the batched sparse sweep therefore opens its scope outside its vmap too.

Host work is named with :func:`annotate` (a ``TraceAnnotation``), and the
phases of a graph's ingest with :func:`ingest_span`, which also records the
phase's seconds in the metrics registry.  :func:`trace_session` captures a
``jax.profiler`` trace of a block, so a serving deployment gets the same
timeline the benchmark reads without importing profiler internals.

Only one JAX profiler trace can run per process; nested ``trace_session``
blocks therefore no-op (the outer session owns the capture) instead of
crashing the serving loop that asked for a second window.
"""
from __future__ import annotations

import contextlib
import functools
import threading
from time import perf_counter

import jax

from .metrics import get_registry

__all__ = ["trace_session", "annotate", "ingest_span", "scoped"]

_active = threading.local()


@contextlib.contextmanager
def trace_session(trace_dir: str, *, label: str | None = None):
    """Capture a ``jax.profiler`` trace of the enclosed block into ``trace_dir``.

    Everything executed inside the block lands in one TensorBoard-loadable
    trace under ``trace_dir`` — jitted computations with their
    ``jax.named_scope`` names (the module docstring lists the engine's),
    host-side gaps between dispatches, and any nested :func:`annotate`
    spans.  ``label`` wraps the whole session in one ``TraceAnnotation``
    span so multiple sessions in one trace directory stay tellable apart.

    Re-entrant use (a session inside a session) yields without starting a
    second capture — the outer session already records everything — so a
    serving drain loop can be wrapped unconditionally.  View with
    ``tensorboard --logdir trace_dir`` (Profile plugin) or Perfetto.
    """
    if getattr(_active, "on", False):
        with annotate(label) if label else contextlib.nullcontext():
            yield
        return
    _active.on = True
    jax.profiler.start_trace(trace_dir)
    try:
        with annotate(label) if label else contextlib.nullcontext():
            yield
    finally:
        _active.on = False
        jax.profiler.stop_trace()


def annotate(label: str):
    """A named host-side span (``jax.profiler.TraceAnnotation``).

    Visible in the trace timeline only while a :func:`trace_session` (or a
    bench ``--profile`` capture) is active; free otherwise.  The bench
    harness wraps each benchmark in one of these, and a serving loop can
    annotate individual flushes the same way.
    """
    return jax.profiler.TraceAnnotation(label)


@contextlib.contextmanager
def ingest_span(phase: str):
    """One phase of a graph's ingest: the host span ``sage.ingest.<phase>``
    and, when the registry is enabled, the phase's seconds in the gauge
    ``sage_ingest_last_seconds{phase=<phase>}`` (the last ingest's, not a
    sum over ingests).  Under ``noop_registry()`` no clock is read beyond
    the annotation's own.  The phase ends when its host work returns: an
    upload it dispatched may still be in flight.
    """
    reg = get_registry()
    with annotate(f"sage.ingest.{phase}"):
        if not reg.enabled:
            yield
            return
        t0 = perf_counter()
        yield
        seconds = perf_counter() - t0
    reg.gauge(
        "sage_ingest_last_seconds",
        "seconds of each phase of the last graph ingest",
        labels=("phase",),
    ).set(seconds, phase=phase)


def scoped(name: str):
    """Decorator: trace the function under ``jax.named_scope(name)``.

    A fresh scope object per call: ``jax.named_scope`` used as a decorator
    keeps one object, whose saved name stack nested or concurrent traces
    would overwrite."""

    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return call

    return wrap
