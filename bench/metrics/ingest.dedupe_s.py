"""Host seconds of the dedupe phase of ``build_csr`` (symmetrize, drop
loops, ``np.unique``, gather): the gauge
``sage_ingest_last_seconds{phase="dedupe"}`` that the program's span
``sage.ingest.dedupe`` sets (``repro.obs.ingest_span``), read from the
registry of the run's own process, which holds the last ingest."""
import math

PHASE = "dedupe"


def read(record):
    # a run on a chip only, as the device metrics: the record then carries
    # the chip's peaks
    if record["peaks"] is None:
        return None
    from repro.obs import get_registry

    gauge = get_registry().get("sage_ingest_last_seconds")
    if gauge is None:
        return None
    seconds = gauge.value(phase=PHASE)
    return None if math.isnan(seconds) else seconds
