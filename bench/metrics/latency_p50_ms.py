"""latency_p50_ms: median request latency, from the time each request
was due to its resolution, over every request of the window."""

import numpy as np


def read(run):
    lat = [r.latency_s for r in run.requests]
    return float(np.percentile(lat, 50) * 1e3) if lat else None
