"""Median of the engine's ``execute`` phase (executor dispatch to results
ready on the host) in the window, ms."""
import numpy as np


def read(run):
    v = run.phase("execute")
    return float(np.median(v)) if v else None
