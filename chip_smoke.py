#!/usr/bin/env python3
"""Serve the repo's CNN path once on a TPU and check every logit.

Default (one chip), two phases through the entry points a user calls —
``serve_cnn.build_qnet`` -> ``CNNServer`` (``api.Accelerator(backend=
"kernels").compile``) -> ``MicroBatchQueue``, untuned compiled Pallas
plans, random weights from ``--seed``:

  vgg11  VGG-11 at its published widths (224x224x3, width 1.0, 100
         classes), radix T=4, fused dataflow, buckets (1, 8).
  lenet5 LeNet-5 at full width, radix T=4, bitserial dataflow (the
         occupancy-gated ``lax.cond`` plane passes), buckets (1, 8).

Each phase sends 16 requests of 1-8 images and checks: every ticket
resolved with logits, no plan failures, one compile per bucket and none
after warmup, and logits bit-equal to ``api.oracle(qnet, x,
mode="packed")`` on the same images.

``--chips 4`` runs only the data-parallel phase: VGG-11 at bucket 8 with
``data_parallel=4``, whose output must be sharded over all four devices
and bit-equal to the single-device plan and to the oracle.

The last line of stdout is ``{"ok": true, "device": {...}}``; it is
printed only when every check passed.  Without a TPU (or without the
repo's ``src/`` next to this file) the script exits non-zero and prints
no result.  Everything runs in this one process.

Usage:
  python3 chip_smoke.py [--seed 0]
  python3 chip_smoke.py --chips 4
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

SRC = pathlib.Path(__file__).resolve().parent / "src"
BUCKETS = (1, 8)
REQUESTS = 16
MAX_REQUEST = 8


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CheckFailed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)
    log(f"  ok: {what}")


def oracle_logits(qnet, x, chunk: int = 8):
    """Reference logits for ``x``, evaluated ``chunk`` rows at a time
    (zero-padded), so the un-jitted oracle compiles one shape only."""
    from repro import api

    outs = []
    for off in range(0, x.shape[0], chunk):
        part = x[off:off + chunk]
        pad = chunk - part.shape[0]
        if pad:
            part = np.concatenate(
                [part, np.zeros((pad,) + part.shape[1:], part.dtype)])
        outs.append(np.asarray(api.oracle(qnet, part, mode="packed"))
                    [:chunk - pad])
    return np.concatenate(outs)


def serve_phase(name, *, arch, dataflow, seed):
    """Build, compile, serve a request stream and check it (one chip)."""
    from repro.launch import serve_cnn

    log(f"phase {name}: {arch} dataflow={dataflow} buckets={BUCKETS}")
    t0 = time.monotonic()
    qnet, item = serve_cnn.build_qnet(arch, seed=seed)
    t_build = time.monotonic() - t0
    server = serve_cnn.CNNServer(qnet, item, buckets=BUCKETS,
                                 dataflow=dataflow)
    weights = [qp["w_q"] for qp in qnet.qlayers if qp is not None]
    log(f"  setup {time.monotonic() - t0:.1f}s (build_qnet {t_build:.1f}s, "
        f"server {time.monotonic() - t0 - t_build:.1f}s): item={item} "
        f"classes={weights[-1].shape[-1]} "
        f"int weights={sum(int(w.size) for w in weights)} "
        f"encoding={server.exe.encoding}")

    t0 = time.monotonic()
    server.warmup()
    warm = server.stats()
    log(f"  compile {time.monotonic() - t0:.1f}s: "
        f"compiles={warm['compiles']}")

    rng = np.random.default_rng(seed)
    sizes = [int(n) for n in rng.integers(1, MAX_REQUEST + 1, REQUESTS)]
    xs = [rng.uniform(0, 1, (n,) + item).astype(np.float32) for n in sizes]
    queue = serve_cnn.MicroBatchQueue(server, timeout_s=0.002)
    t0 = time.monotonic()
    tickets = [queue.submit(x) for x in xs]
    queue.flush()
    for t in tickets:
        if t.ok:
            t.result.block_until_ready()
    wall = time.monotonic() - t0
    stats = server.stats()
    images = sum(t.size for t in tickets if t.ok)
    log(f"  serve {wall:.2f}s: {len(tickets)} requests, {images} images, "
        f"{queue.flushes} flushes, padded_rows={stats['padded_rows']}")

    check(all(t.ok for t in tickets),
          f"all {len(tickets)} tickets resolved with logits")
    check(stats["failures"] == 0, "stats failures == 0")
    check(warm["compiles"] == len(BUCKETS),
          f"compiles == {len(BUCKETS)} buckets after warmup")
    check(stats["compiles"] == warm["compiles"],
          "0 recompiles after warmup")

    t0 = time.monotonic()
    got = np.concatenate([np.asarray(t.result) for t in tickets])
    want = oracle_logits(qnet, np.concatenate(xs))
    log(f"  oracle {time.monotonic() - t0:.1f}s: {want.shape[0]} images")
    check(got.shape == want.shape == (images, want.shape[1]),
          f"logits shape {got.shape}")
    check(bool(np.isfinite(got).all()), "logits finite")
    check(bool(np.array_equal(got, want)),
          "logits bit-equal to api.oracle(mode='packed')")


def data_parallel_phase(*, seed, shards):
    """VGG-11 at bucket 8, data-parallel over ``shards`` devices, against
    the single-device plan and the oracle."""
    import jax

    from repro import api
    from repro.launch import serve_cnn

    bucket = 8
    log(f"phase data_parallel: vgg11 bucket={bucket} "
        f"data_parallel={shards}")
    t0 = time.monotonic()
    qnet, item = serve_cnn.build_qnet("vgg11", seed=seed)
    t_build = time.monotonic() - t0
    acc = api.Accelerator(backend="kernels")
    exe_dp = acc.compile(qnet, item, parallel=shards, buckets=(bucket,))
    exe_1 = acc.compile(qnet, item, parallel=1, buckets=(bucket,))
    log(f"  setup {time.monotonic() - t0:.1f}s (build_qnet {t_build:.1f}s)")

    t0 = time.monotonic()
    exe_dp.warmup()
    exe_1.warmup()
    log(f"  compile {time.monotonic() - t0:.1f}s")

    x = np.random.default_rng(seed).uniform(
        0, 1, (bucket,) + item).astype(np.float32)
    t0 = time.monotonic()
    out_dp = exe_dp.plan_for(bucket)(jax.numpy.asarray(x))
    out_dp.block_until_ready()
    out_1 = exe_1.plan_for(bucket)(jax.numpy.asarray(x))
    out_1.block_until_ready()
    log(f"  run {time.monotonic() - t0:.2f}s")
    devices = out_dp.sharding.device_set
    log(f"  data-parallel output on devices "
        f"{sorted(d.id for d in devices)}")
    check(exe_dp.plan_for(bucket).data_parallel == shards,
          f"plan shards the batch {shards} ways")
    check(len(devices) == shards, f"output sharded over {shards} devices")
    check(exe_dp.stats()["failures"] == 0 and exe_1.stats()["failures"] == 0,
          "stats failures == 0")
    got, single = np.asarray(out_dp), np.asarray(out_1)
    want = oracle_logits(qnet, x)
    check(bool(np.isfinite(got).all()), "logits finite")
    check(bool(np.array_equal(got, single)),
          "data-parallel logits bit-equal to the single-device plan")
    check(bool(np.array_equal(got, want)),
          "data-parallel logits bit-equal to api.oracle(mode='packed')")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: serve VGG-11 and LeNet-5 on one chip; 4: the "
                         "data-parallel VGG-11 phase only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: the repo's sources are not at {SRC}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1
    from repro.launch import compile_cache

    cache_dir = compile_cache.enable()
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__} "
        f"compile_cache={cache_dir}")

    t0 = time.monotonic()
    try:
        if args.chips == 1:
            serve_phase("vgg11", arch="vgg11", dataflow="fused",
                        seed=args.seed)
            serve_phase("lenet5", arch="lenet5", dataflow="bitserial",
                        seed=args.seed)
        else:
            data_parallel_phase(seed=args.seed, shards=args.chips)
    except CheckFailed as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    log(f"all phases passed in {time.monotonic() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
