"""Device milliseconds per round of the compressed graph's decode: the
device time of ops under ``sage.decode`` (``decode_blocks`` and the
per-tile decoders, ``core/compressed.py``), over the rounds that the
window's jobs ran.  A decode op that XLA fuses into a fusion rooted
outside the scope, such as the dense sweep's scatter, is that fusion's
time and not counted here (PERF.md lists which)."""

SCOPES = ("sage.decode",)


def read(record):
    tr, rounds = record["trace"], record["window"]["rounds"]
    if not tr or rounds <= 0 or tr["scope_s"]["sage.decode"] <= 0:
        return None
    return 1000.0 * tr["scope_s"]["sage.decode"] / rounds
