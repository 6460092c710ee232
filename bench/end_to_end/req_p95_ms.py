"""95th percentile over every request of the window, from when it was due
to when its answer was ready. Open-loop cells only."""
import numpy as np


def read(rec):
    lat = rec["window"].get("latencies_s")
    if rec["driver"] != "open_loop" or not lat:
        return None
    return 1e3 * float(np.percentile(np.asarray(lat), 95))
