"""Median request latency over all requests of the window: from the
request's delivery to the end of the step that forwarded it."""
import numpy as np


def read(run):
    lat = run.window.latencies_s
    return float(np.percentile(lat, 50)) * 1e3 if lat else None
