"""latency_p95_ms: 95th percentile of the same latencies as
latency_p50_ms."""

import numpy as np


def read(run):
    lat = [r.latency_s for r in run.requests]
    return float(np.percentile(lat, 95) * 1e3) if lat else None
