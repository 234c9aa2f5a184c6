#!/usr/bin/env python3
"""Readings that set the limit of a cell's comparison.

    python3 bench/control.py --workload <cell> --program-seeds 1,2,...,12 \\
        --control-seeds 13,14,15 [--seconds 3]

In one process, runs the cell's short window for each program seed (the
lower reading: what sound runs of the program give) and, for each control
seed, the same window with the control in the program's place: the plain
reference of the configuration's family computed in bfloat16, one
precision step below the float32 that the configuration states for its
epilogue (the upper reading).  Prints each run's compared numbers and a
summary line.  The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys
import types

import run


class ControlServer:
    """Stands where ``CNNServer`` stands: pads each call to its bucket
    (chunking by the top one), runs the bfloat16 reference, slices back."""

    def __init__(self, wl: dict, cfg: dict, weights: dict):
        import jax.numpy as jnp

        import readers
        from repro.runtime import resilience

        self.item_shape = tuple(cfg["input_hw"])
        self.exe = types.SimpleNamespace(buckets=tuple(wl["buckets"]))
        self.resilience = resilience.ResilienceStats()
        self.weights = weights
        self._fwd = readers.family(cfg).make_forward(cfg, jnp.bfloat16)
        self.counts = {"compiles": 0, "padded_rows": 0, "executions": 0,
                       "failures": 0}

    def warmup(self) -> None:
        for b in self.exe.buckets:
            self.infer(self._zeros(b)).block_until_ready()

    def _zeros(self, n):
        import numpy as np
        return np.zeros((n,) + self.item_shape, np.float32)

    def stats(self) -> dict:
        return {**self.counts, **self.resilience.as_dict()}

    def infer(self, x):
        import jax.numpy as jnp
        import numpy as np

        x = np.asarray(x, np.float32)
        top = self.exe.buckets[-1]
        outs = []
        for off in range(0, x.shape[0], top):
            part = x[off:off + top]
            n = part.shape[0]
            b = next(b for b in self.exe.buckets if b >= n)
            if b > n:
                part = np.concatenate([part, self._zeros(b - n)])
                self.counts["padded_rows"] += b - n
            outs.append(self._fwd(self.weights, jnp.asarray(part))[:n])
            self.counts["executions"] += 1
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs)


def control_server(serve_cnn, qnet, wl, cfg, weights):
    server = ControlServer(wl, cfg, weights)
    server.warmup()
    return server


def readings(wl, cfg, seeds, seconds, devices, serve_cnn, make_server):
    out = []
    for seed in seeds:
        res = run.measure(wl, cfg, seed, seconds, False, devices, serve_cnn,
                          metrics=[], make_server=make_server)
        row = {"seed": seed, "correct": res["correct"],
               "attempted": res["attempted"], "failed": res["failed"],
               **{k: c["value"] for k, c in res["checks"].items()},
               "compared": res["checks"]["mismatched_logits"]["compared"]}
        print(json.dumps(row), flush=True)
        out.append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    wl = run.workload(args.workload)
    cfg = run.config(wl["config"])
    serve_cnn = run.import_program()
    devices = run.require_chip(int(wl["chips"])) if serve_cnn else None
    if devices is None:
        return 1
    seeds = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    program = readings(wl, cfg, seeds(args.program_seeds), args.seconds,
                       devices, serve_cnn, run.program_server)
    control = readings(wl, cfg, seeds(args.control_seeds), args.seconds,
                       devices, serve_cnn, control_server)
    key = "mismatched_logits"
    print(json.dumps({
        "workload": args.workload,
        "program": [r[key] for r in program],
        "control": [r[key] for r in control],
        "lower": max((r[key] for r in program), default=None),
        "upper": min((r[key] for r in control), default=None)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
