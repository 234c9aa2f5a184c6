"""Reduce a profiler trace of one run to what the metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
This module reads it with ``jax.profiler.ProfileData`` and computes:

* busy time: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), inside
  the traced window, averaged over the devices;
* the window: from the first to the last of the harness's host spans
  (``run.py``: generate, submit, poll, infer, resolve, wait);
* kernel grouping: device operations whose HLO instruction is named
  after a kernel's entry point (``KERNELS``: XLA names a Pallas custom
  call after the jitted wrapper around it, ``radix_conv2d_pallas.<n>``),
  each attributed to the ``infer`` span that was open while it ran, with
  that span's image count;
* the breakdown: the device operations that took most time, by name, and
  the longest idle gaps, each labelled with the innermost host span open
  at its middle.
"""

from __future__ import annotations

import bisect
import dataclasses
import pathlib
import re
from typing import Dict, List, Optional, Tuple

HOST_SPANS = ("generate", "submit", "poll", "infer", "resolve", "wait")
KERNELS = {"conv": "radix_conv2d_pallas", "matmul": "radix_matmul_pallas"}
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
TOP = 10
# host and device timestamps of one trace disagree by up to about 0.4 ms
# (a v5e trace: LeNet kernels seen up to 0.4 ms before their call began)
CLOCK_TOL_NS = 1_000_000


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: int      # ns
    end: int        # ns
    images: int = 0  # host ``infer`` spans: images in the call


def op_name(text: str) -> str:
    """The HLO instruction's own name: a device event is named by its
    whole instruction (``%radix_conv2d_pallas.8 = u8[...] custom-call(
    ...)``), whose operands may name other kernels."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(path) -> dict:
    """Device op events per device and the harness's host spans of the
    ``.xplane.pb`` under ``path`` (a file or a directory)."""
    from jax.profiler import ProfileData

    path = pathlib.Path(path)
    if path.is_dir():
        files = sorted(path.rglob("*.xplane.pb"))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = files[-1]
    data = ProfileData.from_file(str(path))
    devices: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                evs = devices.setdefault(int(m.group(1)), [])
                for e in line.events:
                    start = int(e.start_ns)
                    evs.append(Event(op_name(e.name), start,
                                     start + int(e.duration_ns)))
            elif not m and plane.name.startswith("/host"):
                for e in line.events:
                    if e.name in HOST_SPANS:
                        start = int(e.start_ns)
                        stats = dict(e.stats)
                        host.append(Event(
                            e.name, start, start + int(e.duration_ns),
                            images=int(stats.get("images", 0) or 0)))
    for evs in devices.values():
        evs.sort(key=lambda e: e.start)
    host.sort(key=lambda e: (e.start, -e.end))
    return {"devices": devices, "host": host}


def merge(intervals) -> List[Tuple[int, int]]:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: List[Tuple[int, int]], lo: int, hi: int):
    """Idle intervals of ``[lo, hi)`` between the merged busy ones."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def span_at(host: List[Event], t: int) -> Optional[Event]:
    """The innermost (latest-starting) host span open at ``t``."""
    best = None
    for ev in host:
        if ev.start > t:
            break
        if ev.end > t and (best is None or ev.start >= best.start):
            best = ev
    return best


def kernel_calls(devices, host, kind: str, tol_ns: int = CLOCK_TOL_NS):
    """Per ``infer`` span: (images, [durations in ns of the ``kind``
    kernel's device events]).  An event belongs to the last span that
    started before it, allowing for the trace's host-to-device clock
    error; an event past its span's end by more than that belongs to
    none."""
    needle = KERNELS[kind] + "."
    infers = [h for h in host if h.name == "infer"]
    starts = [h.start for h in infers]
    out = []
    for dev_events in devices.values():
        calls: Dict[int, List[int]] = {}
        for ev in dev_events:
            if not ev.name.startswith(needle):
                continue
            i = bisect.bisect_right(starts, ev.start + tol_ns) - 1
            if i >= 0 and ev.start <= infers[i].end + tol_ns:
                calls.setdefault(i, []).append(ev.end - ev.start)
        out += [(infers[i].images, durs) for i, durs in sorted(calls.items())]
    return out


def reduce(path, n_devices: int = 1) -> Optional[dict]:
    """What the metric readers take from one trace (None without a
    device event or a host span)."""
    tr = load(path)
    devices = {d: evs for d, evs in tr["devices"].items() if d < n_devices}
    host = tr["host"]
    if not host or not any(devices.values()):
        return None
    lo = min(h.start for h in host)
    hi = max(h.end for h in host)
    window_ns = hi - lo
    busy_ns, all_gaps, op_time = [], [], {}
    for evs in devices.values():
        merged = merge(clip([(e.start, e.end) for e in evs], lo, hi))
        busy_ns.append(sum(e - s for s, e in merged))
        all_gaps += gaps(merged, lo, hi)
        for e in evs:
            if e.end > lo and e.start < hi:
                op_time[e.name] = op_time.get(e.name, 0) + (e.end - e.start)
    all_gaps.sort(key=lambda g: g[0] - g[1])
    labelled = []
    for s, e in all_gaps[:TOP]:
        sp = span_at(host, (s + e) // 2)
        labelled.append([sp.name if sp else "outside spans", (e - s) / 1e9])
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "kernels": {k: kernel_calls(devices, host, k) for k in KERNELS},
        "breakdown": {
            "device_ops": [[name, t / 1e9] for name, t in top_ops],
            "idle_gaps": labelled,
        },
    }

