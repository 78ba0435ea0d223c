"""Median due-to-answer time over every view of the window, drained
views included (host clock)."""
import numpy as np


def read(run):
    lat = run.latencies_s()
    return float(np.percentile(lat, 50) * 1e3) if lat.size else None
