"""plan_call_ms.sat: median host time of one server call, from
``CNNServer.infer`` through ``block_until_ready`` (input transfer, plan,
slicing back).  Read in the traced run, so the calls are timed under the
profiler."""

import numpy as np


def read(run):
    d = [t1 - t0 for t0, t1, _ in run.calls]
    return float(np.median(d) * 1e3) if d else None
