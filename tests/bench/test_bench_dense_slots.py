"""The reader of ``sweep.dense_slot_pct``: the dense sweep's scattered slots
over its graph's padded slots, from the gauge the program sets when it
traces a dense sweep."""
import os

import pytest

from bench import harness
from benchkit import REPO

PEAKS = {"hbm_bytes_per_s": 819e9}


def _read(slots, peaks=PEAKS):
    """The reader's value in a process whose registry holds ``slots``
    ({kind: value}; None: no gauge at all)."""
    from repro.obs import Registry, use_registry

    reader = harness.load_module(
        os.path.join(REPO, "bench", "metrics", "sweep.dense_slot_pct.py"),
        "bench_metric_sweep_dense_slot_pct",
    )
    reg = Registry()
    if slots is not None:
        gauge = reg.gauge("sage_dense_sweep_slots", labels=("kind",))
        for kind, value in slots.items():
            gauge.set(value, kind=kind)
    with use_registry(reg):
        return reader.read({"trace": None, "window": {"jobs": 1, "rounds": 1},
                            "peaks": peaks})


@pytest.mark.parametrize(
    "slots,peaks,value",
    [
        ({"scattered": 76_073_984, "padded": 202_022_656}, PEAKS, 37.656164663036606),
        # the padded path scatters every slot
        ({"scattered": 1024, "padded": 1024}, PEAKS, 100.0),
        # a program that sets no gauge (the parent), or half of it
        (None, PEAKS, None),
        ({"padded": 1024}, PEAKS, None),
        # a run on the CPU carries no peaks and reports nothing
        ({"scattered": 512, "padded": 1024}, None, None),
    ],
    ids=["banded", "padded", "no_gauge", "half_gauge", "cpu"],
)
def test_reader(slots, peaks, value):
    got = _read(slots, peaks)
    assert got == (None if value is None else pytest.approx(value))


def test_reads_the_gauge_a_traced_dense_sweep_sets():
    """End to end on the CPU: trace a dense sweep over a banded graph, then
    read the metric from the same registry."""
    import jax
    import numpy as np

    from repro.core import build_csr
    from repro.core.edgemap import edgemap_dense
    from repro.obs import Registry, use_registry

    rng = np.random.default_rng(0)
    g = build_csr(300, rng.integers(0, 300, 900), rng.integers(0, 300, 900),
                  block_size=128)
    reg = Registry()
    with use_registry(reg):
        jax.jit(edgemap_dense).lower(g, np.ones(300, bool), np.zeros(300, np.int32))
        gauge = reg.get("sage_dense_sweep_slots")
        padded = g.num_blocks * g.block_size
        assert gauge.value(kind="padded") == padded
    pct = _read({k: gauge.value(kind=k) for k in ("scattered", "padded")})
    assert 0 < pct < 100
