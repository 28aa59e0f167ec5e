"""Device seconds per job of the chunked (sparse) sweep: the device time
of ops under ``sage.sweep.sparse``, the scope of ``edgemap_chunked`` and
its batched and streamed variants (``core/edgemap.py``), over the window's
jobs.  Per job, as ``sweep.dense_s``."""

SCOPES = ("sage.sweep.sparse",)


def read(record):
    tr = record["trace"]
    if not tr or tr["scope_s"]["sage.sweep.sparse"] <= 0:
        return None
    return tr["scope_s"]["sage.sweep.sparse"] / record["window"]["jobs"]
