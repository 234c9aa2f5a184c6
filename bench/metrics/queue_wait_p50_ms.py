"""queue_wait_p50_ms: median time from a request's due time to the start
of the flush (the server call) that carried it."""

import bisect

import numpy as np


def read(run):
    starts = [t0 for t0, _, _ in run.calls]
    waits = []
    for r in run.requests:
        if not r.ticket.ok:
            continue
        i = bisect.bisect_right(starts, r.resolved) - 1
        if i >= 0:
            waits.append(starts[i] - r.due)
    return float(np.percentile(waits, 50) * 1e3) if waits else None
