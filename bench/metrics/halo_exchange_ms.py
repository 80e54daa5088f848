"""Device milliseconds of one reference halo exchange of the acoustic
state (u, v, delp, pt, w), called alone (device trace)."""


def read(record):
    probe = (record["trace"] or {}).get("probes", {}).get("halo_exchange")
    if not probe or probe["calls"] == 0:
        return None
    return 1e3 * probe["device_s"] / probe["calls"]
