"""latency_p95_ms: the 95th percentile (nearest rank), over every request
due in the window, of the time from its due time until its answer was on
the host.  A request that failed or never came back counts as infinite."""
import math


def read(run):
    lat = sorted(r.latency for r in run.window.records)
    if not lat:
        return None
    return 1e3 * lat[math.ceil(0.95 * len(lat)) - 1]
