"""Mean over the window's requests of the wait from submit to dispatch,
from the service's own ``ServeMetrics`` queue-wait samples."""
import numpy as np


def read(rec):
    waits = rec.get("serve", {}).get("queue_wait_s")
    if not waits:
        return None
    return 1e3 * float(np.mean(waits))
