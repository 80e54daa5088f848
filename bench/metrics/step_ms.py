"""Wall time of the measured window over the physics steps it completed:
from the first dispatch until every step sent has finished (host clock)."""


def read(record):
    host = record["host"]
    if not host.get("steps"):
        return None
    return 1e3 * host["window_s"] / host["steps"]
