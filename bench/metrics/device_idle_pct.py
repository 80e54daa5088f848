"""Share of the traced whole-step window in which no op ran on the
device: 1 - (union of op intervals) / window (device trace)."""


def read(record):
    trace = record["trace"]
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
