"""90th percentile of query latency over every query completed in the
window, from the client's send to the answer being on the device and
ready (``block_until_ready`` in the client thread)."""
import numpy as np


def read(run):
    lat = [q.t_done - q.t_send for q in run.queries]
    return 1e3 * float(np.percentile(lat, 90)) if lat else None
