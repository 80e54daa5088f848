"""Device milliseconds a step spends in XLA's own ops (busy time that no
Mosaic kernel covers) in the whole-step trace."""


def read(record):
    trace = record["trace"]
    if not trace:
        return None
    return 1e3 * trace["xla_s"] / trace["steps"]
