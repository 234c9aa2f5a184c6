#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest Poisson rate at which the
backlog does not grow over a window.

    python3 bench/sweep.py --workload <cell> --rates 250,500,1000 \\
        [--seconds 5] [--seed 1]

Builds the cell once, then offers each rate for ``--seconds`` and prints,
per rate, the latencies, how late the generator ran, and the median
latency of the window's last quarter of requests against its first: a
backlog that grows shows as a last quarter far above the first.  Run it
on the chip; write 0.8 x the knee into the cell's file as ``rate_per_s``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import run
import traffic


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    wl = run.workload(args.workload)
    cfg = run.config(wl["config"])
    serve_cnn = run.import_program()
    devices = run.require_chip(int(wl["chips"])) if serve_cnn else None
    if devices is None:
        return 1
    from repro.launch import compile_cache

    import jax
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    _, _, server, pool = run.build(wl, cfg, args.seed, serve_cnn)
    clock = time.perf_counter
    spans = run.Spans(False)
    compiles = run.CompileCounter()
    rows = []
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        timed = run.TimedServer(server, clock, spans)
        queue = serve_cnn.MicroBatchQueue(
            timed, timeout_s=float(wl["timeout_ms"]) / 1e3, clock=clock,
            health=run.health_monitor())
        sched = traffic.open_schedule(wl, args.seconds, args.seed, 100 + k,
                                      rate_per_s=rate)
        compiled = compiles.count
        t0 = clock()
        reqs = run.open_loop(queue, timed, sched, pool, t0, clock, spans)
        lat = np.array([r.latency_s for r in reqs]) * 1e3
        late = np.array([r.sent - r.due for r in reqs]) * 1e3
        q = max(1, len(reqs) // 4)
        row = {"rate": rate, "requests": len(reqs),
               "failed": sum(not r.ticket.ok for r in reqs),
               "p50_ms": float(np.percentile(lat, 50)),
               "p95_ms": float(np.percentile(lat, 95)),
               "first_q_p50_ms": float(np.median(lat[:q])),
               "last_q_p50_ms": float(np.median(lat[-q:])),
               "late_p95_ms": float(np.percentile(late, 95)),
               "late_max_ms": float(late.max()),
               "flushes": len(timed.calls),
               "images_per_flush": float(np.mean([n for _, _, n
                                                  in timed.calls])),
               "drain_s": max(r.resolved for r in reqs) - t0 - args.seconds,
               "compiled": compiles.count - compiled}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"sweep": args.workload, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
