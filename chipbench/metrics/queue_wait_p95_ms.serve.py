"""95th percentile of the time requests waited in the server's admission
queue, ``Solution.timings["queue_wait"]`` (arrival to bundle flush, on
the server's monotonic clock), over the window's answered requests
(serving layer, ``serve/solver``)."""
import numpy as np


def read(rec):
    waits = rec.samples.get("queue_wait_s")
    if not waits:
        return None
    return 1e3 * float(np.percentile(waits, 95))
