"""Metric readers: ``<metric>.py`` per metric of ``BENCHMARK.json``.

Each has ``read(record) -> float | None`` and, where it reads device time
under named scopes, ``SCOPES``.  ``record`` is the run's record (see
``bench.harness.run``): its set-up times, window, graph, configuration,
traffic, peaks and, in a traced run, the reduced trace.  A reader that
finds nothing to read returns None, and the metric is left out.
"""
