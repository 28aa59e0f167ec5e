"""Device seconds per job of the dense (pull) sweep: the device time of
ops under ``sage.sweep.dense``, the scope of ``edgemap_dense`` and
``edgemap_dense_batched`` (``core/edgemap.py``), over the window's jobs.
Per job and not per round: the program counts no rounds by mode inside a
jitted loop, and the BFS window always holds the same pooled searches."""

SCOPES = ("sage.sweep.dense",)


def read(record):
    tr = record["trace"]
    if not tr or tr["scope_s"]["sage.sweep.dense"] <= 0:
        return None
    return tr["scope_s"]["sage.sweep.dense"] / record["window"]["jobs"]
