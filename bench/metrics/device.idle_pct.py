"""Share of the traced window in which no operation ran on the device:
100 * (1 - busy / window), busy the union of device op intervals."""


def read(record):
    tr = record["trace"]
    if not tr or not tr["chips"] or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
