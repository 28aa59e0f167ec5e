"""Share of the padded slots that the dense sweep scatters, in %: 100 ×
the gauge ``sage_dense_sweep_slots{kind="scattered"}`` over
``{kind="padded"}``, which ``core/edgemap.py`` sets when it traces a dense
sweep, read from the registry of the run's own process, which traced the
cell's program.  100 where the sweep takes the padded path; below it where
the graph's degree bands drop the padding past each band's width."""
import math


def read(record):
    # a run on a chip only, as the ingest's phases
    if record["peaks"] is None:
        return None
    from repro.obs import get_registry

    gauge = get_registry().get("sage_dense_sweep_slots")
    if gauge is None:
        return None
    scattered, padded = gauge.value(kind="scattered"), gauge.value(kind="padded")
    if math.isnan(scattered) or math.isnan(padded) or padded <= 0:
        return None
    return 100.0 * scattered / padded
