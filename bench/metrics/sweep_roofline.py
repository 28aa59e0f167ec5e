"""Share of the HBM roofline that the PageRank sweep reaches.

The sweep is memory-bound: the least time it could take is the bytes the
job needs over the chip's peak HBM bandwidth.  The bytes come from the
configuration's declared encoding and the graph's shape, not from the
program's arrays, so they are the same whatever implements the sweep.
Per iteration: one target of ``target_bytes`` per directed edge, and n
words each of rank in, rank out and degree.  Padding and exceptions are the
implementation's cost, not work the job needs.  Share = bytes of the
window's jobs / (seconds under ``sage.round`` * peak bytes per second)."""

SCOPES = ("sage.round",)
WORD = 4


def iteration_bytes(config: dict, n: int, m_directed: int) -> int:
    return int(config["encoding"]["target_bytes"]) * m_directed + 3 * WORD * n


def read(record):
    tr, peaks = record["trace"], record["peaks"]
    if not tr or not peaks or record["traffic"]["job"] != "pagerank":
        return None
    sweep = tr["scope_s"]["sage.round"]
    if sweep <= 0:
        return None
    g = record["graph"]
    per_job = int(record["traffic"]["iterations"]) * iteration_bytes(
        record["config"], g.n, g.m_directed)
    needed = per_job * record["window"]["jobs"]
    return 100.0 * needed / (sweep * float(peaks["hbm_bytes_per_s"]))
