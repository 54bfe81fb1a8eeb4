"""99th-percentile request latency over all requests of the window, from
the request's delivery to the end of the step that forwarded it. In a
closed loop at capacity every request waits one scheduling round, so this
is the window's slowest round, and one host stall sets it: a reading of
the scheduler's rounds, not an end-to-end bound."""
import numpy as np


def read(run):
    lat = run.window.latencies_s
    return float(np.percentile(lat, 99)) * 1e3 if lat else None
