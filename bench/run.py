#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print one JSON result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything happens in this one process, on the served path:
``MicroBatchQueue.submit/poll/flush`` -> ``CNNServer.infer`` ->
``api.Executable`` (``PlanCache``: pad, chunk, slice) -> ``CompiledPlan``
(the Pallas kernels).  The cell's file (``bench/workloads/<cell>.json``)
names its configuration (``bench/configs/<config>.json``) and its traffic;
the metrics a cell reports are the entries of ``BENCHMARK.json`` that
list it, each read by ``bench/metrics/<metric>.py``.

Set-up (reported as ``setup_s``) runs from process start to the opening
of the window: imports, the seeded weights, the server and its bucket
plans, every shape the traffic can produce, the input pool and a short
warm-in of traffic.  The window then runs for ``--seconds`` (with
``--trace 1``, for the cell's ``trace_seconds`` under the profiler).
After it closes, the device's peak memory is read, the program is freed
and the logits of a seeded sample of the window's requests are compared
with the plain reference (``bench/reference.py``).

The run refuses any platform but ``tpu`` and exits non-zero, printing no
result, when JAX finds no chip, too few chips, or no program next to the
benchmark.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import List, Optional  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import numpy as np  # noqa: E402

import readers  # noqa: E402
import traffic  # noqa: E402


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def workload(name: str) -> dict:
    wl = load_json(BENCH / "workloads" / f"{name}.json")
    wl["name"] = name
    return wl


def config(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def cell_metrics(cell: str, trace: bool) -> List[dict]:
    """The metrics of ``BENCHMARK.json`` this cell reports in this kind
    of run: end-to-end ones untraced, per-layer ones traced."""
    spec = load_json(ROOT / "BENCHMARK.json")
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


# ---------------------------------------------------------------------------
# Host spans: profiler annotations in traced runs, nothing otherwise.
# ---------------------------------------------------------------------------


class Spans:
    def __init__(self, on: bool):
        self.on = on
        if on:
            import jax
            self._ann = jax.profiler.TraceAnnotation

    def __call__(self, name: str, **kw):
        return self._ann(name, **kw) if self.on else contextlib.nullcontext()

    def open(self, name: str, **kw):
        if not self.on:
            return None
        ann = self._ann(name, **kw)
        ann.__enter__()
        return ann


class TimedServer:
    """The queue's server, with each ``infer`` timed on the host clock
    through ``block_until_ready``; the rest is the real ``CNNServer``."""

    def __init__(self, server, clock, spans: Spans):
        self.server = server
        self.item_shape = server.item_shape
        self.exe = server.exe
        self.resilience = server.resilience
        self.clock = clock
        self.spans = spans
        self.calls: list = []       # (start, end, images)
        self._resolve = None

    def end_resolve(self) -> None:
        """Close the span from the last infer's return to the end of the
        queue call that made it: the queue handing tickets their rows."""
        if self._resolve is not None:
            self._resolve.__exit__(None, None, None)
            self._resolve = None

    def infer(self, x):
        self.end_resolve()
        n = int(x.shape[0])
        t0 = self.clock()
        with self.spans("infer", images=n):
            out = self.server.infer(x)
            out.block_until_ready()
        self.calls.append((t0, self.clock(), n))
        self._resolve = self.spans.open("resolve")
        return out


@dataclasses.dataclass
class Request:
    due: float
    sent: float
    ticket: object
    images: int
    pool_offset: int

    @property
    def resolved(self) -> float:
        t = self.ticket
        return t.t_submit + (t.latency_s if t.latency_s is not None
                             else math.inf)

    @property
    def latency_s(self) -> float:
        return self.resolved - self.due


def wait_until(target: float, clock, spans: Spans) -> None:
    now = clock()
    if target - now > 0.001:
        with spans("wait"):
            time.sleep(target - now - 0.0005)
    while clock() < target:
        pass


def open_loop(queue, server: TimedServer, schedule, pool, t0: float,
              clock, spans: Spans) -> List[Request]:
    """Send ``schedule`` on time whatever the queue does; poll when the
    oldest pending request's timeout passes; return when all resolved."""
    reqs: List[Request] = []
    i, oldest, n = 0, 0, len(schedule)
    timeout = queue.timeout_s
    while True:
        now = clock()
        if i < n and now >= t0 + schedule[i].t:
            a = schedule[i]
            with spans("generate"):
                x = pool[a.pool_offset:a.pool_offset + a.images]
            sent = clock()
            with spans("submit", images=a.images):
                ticket = queue.submit(x)
                server.end_resolve()
            reqs.append(Request(t0 + a.t, sent, ticket, a.images,
                                a.pool_offset))
            i += 1
            continue
        while oldest < len(reqs) and reqs[oldest].ticket.done:
            oldest += 1
        if i >= n and oldest >= len(reqs):
            return reqs
        t_flush = (reqs[oldest].ticket.t_submit + timeout
                   if oldest < len(reqs) else math.inf)
        if now >= t_flush:
            with spans("poll"):
                queue.poll()
                server.end_resolve()
            continue
        t_next = t0 + schedule[i].t if i < n else math.inf
        wait_until(min(t_flush, t_next), clock, spans)


def closed_loop(queue, server: TimedServer, source, pool, clients: int,
                t_end: float, clock, spans: Spans) -> List[Request]:
    """``clients`` callers, each sending its next request as soon as its
    last one resolved, until ``t_end``; return when all resolved."""
    reqs: List[Request] = []
    active: List[Optional[Request]] = [None] * clients
    timeout = queue.timeout_s
    while True:
        sent_any = False
        for c in range(clients):
            r = active[c]
            if r is not None and not r.ticket.done:
                continue
            if clock() >= t_end:
                active[c] = None
                continue
            a = source.next()
            with spans("generate"):
                x = pool[a.pool_offset:a.pool_offset + a.images]
            sent = clock()
            with spans("submit", images=a.images):
                ticket = queue.submit(x)
                server.end_resolve()
            active[c] = Request(sent, sent, ticket, a.images, a.pool_offset)
            reqs.append(active[c])
            sent_any = True
        waiting = [r for r in active if r is not None and not r.ticket.done]
        if not waiting:
            if all(r is None for r in active):
                return reqs
            continue
        if sent_any:
            continue
        t_flush = min(r.ticket.t_submit for r in waiting) + timeout
        if clock() >= t_flush:
            with spans("poll"):
                queue.poll()
                server.end_resolve()
        else:
            wait_until(t_flush, clock, spans)


# ---------------------------------------------------------------------------
# One run.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Run:
    """What the metric readers read (``bench/metrics/*.py``)."""

    cfg: dict
    setup_s: float
    t0: float
    t_end: float
    requests: List[Request]
    calls: list                  # TimedServer.calls in the window
    stats0: dict
    stats1: dict
    chips: int
    peak: dict                   # peaks of one chip
    trace: Optional[dict] = None  # trace reduction (trace.reduce)


def require_chip(chips: int):
    """The devices of a chip run; None (after saying why) where JAX finds
    no TPU or fewer chips than the cell asks for."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"needs a TPU, but JAX found platform {devices[0].platform!r} "
            f"({devices[0].device_kind}); no result")
        return None
    if len(devices) < chips:
        log(f"the cell needs {chips} chips, JAX found {len(devices)}; "
            "no result")
        return None
    return devices[:chips]


def import_program():
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        return None
    sys.path.insert(0, str(src))
    from repro.launch import serve_cnn
    return serve_cnn


def warm_shapes(server, wl: dict, item: tuple, threads: int = 8) -> int:
    """Run every flush size the traffic can make through the server, and
    every ticket slice of each, so that nothing compiles in the window.

    The queue slices each ticket's rows with one small program per
    (flush size, offset, size); there are thousands for the 1-8 image
    mix, so they compile (or load from the cache) on a few threads."""
    import concurrent.futures

    import jax.numpy as jnp

    def take(out, o, m):
        out[o:o + m].block_until_ready()

    max_batch = server.exe.buckets[-1]
    outs = []
    for n in traffic.flush_sizes(wl, max_batch):
        out = server.infer(jnp.zeros((n,) + item, jnp.float32))
        out.block_until_ready()
        outs.append((n, out))
    with concurrent.futures.ThreadPoolExecutor(threads) as pool:
        futures = [pool.submit(take, out, o, m) for n, out in outs
                   for o, m in traffic.ticket_slices(wl, n)]
        for f in futures:
            f.result()
    return len(outs) + len(futures)


def health_monitor():
    """The queue's health machine with straggler flagging off: failed
    flushes still degrade and drain the server, slow ones do not.

    The program's default flags any flush slower than its median by a few
    deviations, over all buckets together; a mix of bucket sizes, or one
    host hiccup among like flushes, drains the server for good (PERF.md,
    Open questions)."""
    from repro.runtime import resilience, straggler

    return resilience.HealthMonitor(straggler.StragglerMonitor(
        threshold=math.inf))


def sample_requests(reqs: List[Request], cap: int, seed: int):
    """A seeded sample of resolved requests holding about ``cap`` images,
    the largest request always among them."""
    done = [r for r in reqs if r.ticket.ok]
    if not done:
        return []
    order = traffic.rng(seed, 7).permutation(len(done))
    biggest = max(range(len(done)), key=lambda i: done[i].images)
    picked, images = [biggest], done[biggest].images
    for i in order:
        if images >= cap:
            break
        if i != biggest:
            picked.append(int(i))
            images += done[i].images
    return [done[i] for i in sorted(picked)]


def compare(cfg: dict, weights: dict, pool, sample, chunk: int, dtype=None):
    """Reference logits for every sampled request beside what the window
    delivered: (mismatched logits, compared logits)."""
    import jax.numpy as jnp

    import reference

    if not sample:
        return 0, 0
    fwd = reference.make_forward(cfg, dtype=dtype or jnp.float32)
    rows = np.concatenate([np.arange(r.pool_offset, r.pool_offset + r.images)
                           for r in sample])
    got = np.concatenate([r.logits for r in sample])
    want = []
    for off in range(0, len(rows), chunk):
        idx = rows[off:off + chunk]
        x = pool[idx]
        pad = chunk - len(idx)
        if pad:
            x = np.concatenate([x, np.zeros((pad,) + x.shape[1:], x.dtype)])
        want.append(np.asarray(fwd(weights, jnp.asarray(x)))[:len(idx)])
    want = np.concatenate(want)
    same = got == want
    return int((~same).sum()), int(same.size)


def program_server(serve_cnn, qnet, wl: dict, cfg: dict, weights: dict):
    """The system under test: the program's ``CNNServer`` with the cell's
    bucket ladder, dataflow and chips."""
    return serve_cnn.CNNServer(qnet, tuple(cfg["input_hw"]),
                               buckets=wl["buckets"],
                               dataflow=cfg["dataflow"],
                               data_parallel=int(wl["chips"]))


def build(wl: dict, cfg: dict, seed: int, serve_cnn,
          make_server=program_server):
    """The seeded weights, the program's net and server with every shape
    of the cell's traffic compiled, and the host pool of input images.
    ``make_server`` is replaced only by the control (``control.py``)."""
    import jax

    import netgen

    clock = time.perf_counter
    key = netgen.root_key(seed)
    t = clock()
    weights = netgen.make_weights(cfg, jax.random.fold_in(key, 0))
    jax.block_until_ready(weights)
    qnet = netgen.quantized_net(cfg, weights)
    item = tuple(cfg["input_hw"])
    server = make_server(serve_cnn, qnet, wl, cfg, weights)
    t_weights = clock() - t
    server.warmup()
    t_plans = clock() - t - t_weights
    pool = np.asarray(netgen.images(cfg, jax.random.fold_in(key, 1),
                                    int(wl["pool_images"])))
    t = clock()
    shapes = warm_shapes(server, wl, item)
    log(f"weights {t_weights:.2f} s, bucket plans {t_plans:.2f} s, "
        f"{shapes} traffic shapes {clock() - t:.2f} s")
    return weights, qnet, server, pool


def plan_footprint(server, item: tuple) -> Optional[int]:
    """Device bytes the largest bucket plan adds to what is resident while
    it runs: its input, its scratch and its output, as the compiled
    program reports them (``memory_analysis``).  The allocator's peak
    counts the arrays JAX holds; the scratch an executable takes for its
    activations is the compiled program's to report.  None where the
    server has no such plans (the control)."""
    import jax
    import jax.numpy as jnp

    plan_for = getattr(server.exe, "plan_for", None)
    if plan_for is None:
        return None
    best = 0
    for b in server.exe.buckets:
        plan = plan_for(b)
        fn, params = getattr(plan, "_fn", None), getattr(plan, "_params", None)
        if not hasattr(fn, "lower"):
            return None
        x = jax.ShapeDtypeStruct((b,) + item, jnp.float32)
        mem = fn.lower(params, x).compile().memory_analysis()
        if mem is None:
            return None
        resident = sum(a.nbytes for a in jax.tree_util.tree_leaves(params))
        best = max(best, mem.argument_size_in_bytes - resident
                   + mem.output_size_in_bytes + mem.temp_size_in_bytes
                   - mem.alias_size_in_bytes)
    return best


def memory_peak(devices, footprint: Optional[int]) -> int:
    """Peak bytes on the fullest chip: the allocator's peak, or what is
    resident at the window's close plus the largest plan's footprint,
    whichever is larger."""
    peak = 0
    for d in devices:
        s = d.memory_stats() or {}
        log(f"memory {d}: allocator peak {s.get('peak_bytes_in_use')}, in "
            f"use {s.get('bytes_in_use')}, limit {s.get('bytes_limit')}, "
            f"largest plan's input+scratch+output {footprint}")
        peak = max(peak, int(s.get("peak_bytes_in_use", 0)),
                   int(s.get("bytes_in_use", 0)) + (footprint or 0))
    return peak


def host_usage() -> dict:
    """This process's CPU seconds, page faults and context switches."""
    u = resource.getrusage(resource.RUSAGE_SELF)
    return {"user_s": u.ru_utime, "sys_s": u.ru_stime,
            "minor_faults": u.ru_minflt, "major_faults": u.ru_majflt,
            "voluntary_switches": u.ru_nvcsw,
            "involuntary_switches": u.ru_nivcsw}


def log_window(calls: list, t0: float, t_end: float, usage0: dict) -> None:
    """How the window went, for finding why one run differs from the
    next: images per second in each quarter, the server calls' times, the
    host's time between calls, and what the host did meanwhile."""
    if not calls:
        return
    q = (t_end - t0) / 4
    per_q = [sum(n for _, t1, n in calls
                 if t0 + k * q <= t1 < t0 + (k + 1) * q) / q
             for k in range(4)]
    infer = np.array([t1 - s for s, t1, _ in calls]) * 1e3
    gaps = np.array([b[0] - a[1] for a, b in zip(calls, calls[1:])]) * 1e3
    pct = lambda v: (f"p50 {np.percentile(v, 50):.2f} p95 "  # noqa: E731
                     f"{np.percentile(v, 95):.2f} max {v.max():.2f}"
                     if len(v) else "none")
    usage1 = host_usage()
    log("window: images/s by quarter " + " ".join(f"{r:.1f}" for r in per_q)
        + f"; {len(calls)} server calls ms {pct(infer)}; host between "
        f"calls ms {pct(gaps)}; " + ", ".join(
            f"{k} {usage1[k] - usage0[k]:.6g}" for k in usage1)
        + f"; load average {os.getloadavg()[0]:.2f}")


class CompileCounter:
    """Programs compiled or loaded from the compilation cache, counted
    from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


def measure(wl: dict, cfg: dict, seed: int, seconds: float, trace: bool,
            devices, serve_cnn, *, t_process: float = T_PROCESS,
            metrics: Optional[List[dict]] = None,
            peak: Optional[dict] = None,
            make_server=program_server) -> dict:
    """One run of one cell on ``devices``; returns the result object.

    ``metrics`` (default: the cell's entries of ``BENCHMARK.json``) and
    ``peak`` (default: the peaks table's row for the device) are given
    only by the CPU rehearsal in ``bench/tests``."""
    import jax

    import work
    from repro.launch import compile_cache

    clock = time.perf_counter
    cache_dir = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileCounter()
    chips = int(wl["chips"])
    dev = devices[0]
    log(f"cell {wl['name']}: config {cfg['name']}, seed {seed}, "
        f"{seconds} s, trace {int(trace)}, device {dev.device_kind} x "
        f"{len(devices)}, compile cache {cache_dir}")
    if peak is None:
        peak = work.peaks(dev.device_kind)
    if metrics is None:
        metrics = cell_metrics(wl["name"], trace)

    weights, qnet, server, pool = build(wl, cfg, seed, serve_cnn,
                                        make_server)
    spans = Spans(trace)
    timed = TimedServer(server, clock, spans)
    queue = serve_cnn.MicroBatchQueue(
        timed, timeout_s=float(wl["timeout_ms"]) / 1e3, clock=clock,
        health=health_monitor())
    closed = wl["loop"] == "closed"
    window = min(seconds, float(wl["trace_seconds"])) if trace else seconds
    if closed:
        source = traffic.ClosedSource(wl, seed)

        def drive(stream, length):
            return closed_loop(queue, timed, source, pool,
                               int(wl["clients"]), clock() + length, clock,
                               spans)
    else:
        def drive(stream, length):
            sched = traffic.open_schedule(wl, length, seed, stream)
            return open_loop(queue, timed, sched, pool, clock(), clock,
                             spans)

    t = clock()
    drive(1, float(wl["warmin_s"]))
    t_warmin = clock() - t
    stats0 = server.stats()
    compiles0 = compiles.count, compiles.seconds
    if trace:
        tmp = pathlib.Path(tempfile.mkdtemp(prefix="bench_trace_"))
        jax.profiler.start_trace(str(tmp))
    del timed.calls[:]
    setup_s = clock() - t_process
    log(f"set-up {setup_s:.2f} s, warm-in {t_warmin:.2f} s")

    usage0 = host_usage()
    t0 = clock()
    reqs = drive(0, window)
    t_end = t0 + window
    if trace:
        jax.profiler.stop_trace()
    calls = list(timed.calls)
    stats1 = server.stats()
    log_window(calls, t0, t_end, usage0)
    log(f"queue health {queue.health.state}; " + ", ".join(
        f"{k} {stats1[k] - stats0[k]}" for k in
        ("rejected", "shed", "retried", "quarantined", "degraded_flushes",
         "failures", "padded_rows", "executions")))
    late = np.array([r.sent - r.due for r in reqs]) * 1e3
    if len(late):
        log(f"generator lateness ms: p50 {np.percentile(late, 50):.3f} "
            f"p95 {np.percentile(late, 95):.3f} max {late.max():.3f} "
            f"over {len(late)} requests")
    log(f"in the window: {compiles.count - compiles0[0]} programs compiled "
        f"or loaded ({compiles.seconds - compiles0[1]:.3f} s), "
        f"{stats1['compiles'] - stats0['compiles']} bucket plans compiled")
    peak_bytes = memory_peak(devices,
                             plan_footprint(server, tuple(cfg["input_hw"])))

    sample = sample_requests(reqs, int(wl["check_images"]), seed)
    for r in sample:
        r.logits = np.asarray(r.ticket.result)
    run = Run(cfg=cfg, setup_s=setup_s, t0=t0,
              t_end=t_end, requests=reqs, calls=calls, stats0=stats0,
              stats1=stats1, chips=chips, peak=peak)
    del queue, timed, server, qnet
    for r in reqs:
        r.ticket = _Resolved(r.ticket)
    gc.collect()

    if trace:
        import devtrace
        run.trace = devtrace.reduce(tmp, n_devices=chips)
        shutil.rmtree(tmp, ignore_errors=True)

    t = clock()
    mismatched, compared = compare(cfg, weights, pool, sample,
                                   int(wl["check_chunk"]))
    log(f"reference over {sum(r.images for r in sample)} images of "
        f"{len(sample)} requests: {time.perf_counter() - t:.2f} s")
    failed = sum(1 for r in reqs if not r.ticket.ok)
    checks = {
        "mismatched_logits": {"value": mismatched, "limit": 0,
                              "compared": compared},
    }
    correct = mismatched <= 0 and compared >= 1

    values = {}
    for m in metrics:
        value = readers.load(m["name"])(run)
        if value is not None:
            values[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    result = {"correct": bool(correct), "attempted": len(reqs),
              "failed": failed, "metrics": values, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = run.trace["breakdown"]
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} of {c['compared']} compared "
            f"(limit {c['limit']})")
    return result


class _Resolved:
    """What a ticket said, kept after the program is freed."""

    def __init__(self, ticket):
        self.ok = ticket.ok
        self.done = ticket.done
        self.t_submit = ticket.t_submit
        self.latency_s = ticket.latency_s


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    wl = workload(args.workload)
    cfg = config(wl["config"])
    serve_cnn = import_program()
    if serve_cnn is None:
        log(f"no program under {ROOT / 'src'}; no result")
        return 2
    devices = require_chip(int(wl["chips"]))
    if devices is None:
        return 1
    result = measure(wl, cfg, args.seed, args.seconds, bool(args.trace),
                     devices, serve_cnn)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
