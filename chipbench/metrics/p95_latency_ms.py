"""95th percentile of request latency, every answered request of the
window, from when it was due to when its answer reached the caller."""
import numpy as np


def read(run):
    lat = [(r.done - r.due) * 1e3 for r in run.window.requests.values()
           if r.done is not None]
    return float(np.percentile(lat, 95)) if lat else None
