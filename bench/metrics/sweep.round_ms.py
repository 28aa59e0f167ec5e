"""Device milliseconds per round of the edgeMap sweep: the device time of
ops under ``sage.round``, which the sequential round loop wraps around
exactly the edgeMap call (``core/plan.py::round_loop``), over the rounds
that the window's jobs ran (``rounds`` of the job kind)."""

SCOPES = ("sage.round",)


def read(record):
    tr, rounds = record["trace"], record["window"]["rounds"]
    if not tr or rounds <= 0 or tr["scope_s"]["sage.round"] <= 0:
        return None
    return 1000.0 * tr["scope_s"]["sage.round"] / rounds
