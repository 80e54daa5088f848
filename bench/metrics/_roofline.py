"""Shared arithmetic of the ``roofline_pct.<program>`` readers."""

import re


def roofline_pct(record, program: str):
    """Lower-bound bytes of one call over the HBM peak, as a share of the
    device time of one call of the program's runner probed alone."""
    trace = record["trace"]
    # probes are keyed by the program's name with non-word characters
    # replaced, as their XLA modules are named
    key = re.sub(r"[^A-Za-z0-9_]", "_", program)
    probe = (trace or {}).get("probes", {}).get(key)
    if not probe or probe["calls"] == 0 or probe["device_s"] <= 0:
        return None
    seconds = probe["device_s"] / probe["calls"]
    least = record["bytes"]["per_call"][program] \
        / record["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
