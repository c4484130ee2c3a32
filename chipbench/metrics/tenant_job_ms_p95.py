"""95th percentile (nearest rank) of the latency of every tenant job
submitted in the window, from submission to retirement with its round
budget spent."""
import math

UNIT = "ms"


def read(win):
    lat = sorted(win.samples.get("job_s", []))
    if not lat:
        return None
    return 1000.0 * lat[math.ceil(0.95 * len(lat)) - 1]
