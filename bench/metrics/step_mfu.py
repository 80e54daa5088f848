"""The whole step's share of the chip's peak: the step's lower-bound HBM
bytes (program calls and halo rings) over the HBM peak, divided by the
traced time per step.  For these float32 stencils the bytes bound the
step, so the peak is the HBM bandwidth (device trace)."""


def read(record):
    trace = record["trace"]
    if not trace or trace["window_s"] <= 0:
        return None
    least = record["bytes"]["per_step"] / record["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (trace["window_s"] / trace["steps"])
