"""Median of the engine's ``queue_wait`` phase (submit to the start of the
flush that serves the request) in the window, ms."""
import numpy as np


def read(run):
    v = run.phase("queue_wait")
    return float(np.median(v)) if v else None
