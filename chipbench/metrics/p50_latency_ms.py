"""Median request latency over the same requests as ``p95_latency_ms``."""
import numpy as np


def read(run):
    lat = [(r.done - r.due) * 1e3 for r in run.window.requests.values()
           if r.done is not None]
    return float(np.percentile(lat, 50)) if lat else None
