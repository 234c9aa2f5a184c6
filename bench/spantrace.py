"""Read a run's trace by the program's own spans and plan modules.

``devtrace`` reduces a trace by the harness's host spans.  The program
(``repro.runtime.trace``) also writes spans of its own into the same
profiler session (``queue.submit``, ``queue.assemble``, ``exe.to_device``
...), and names each bucket's plan ``jit_plan_b<bucket>`` on the device's
``XLA Modules`` line, with each layer under a named scope that the
plan's ``layer_map()`` reads back from the compiled program.  This module
computes, from one trace:

* ``span_ms``: the median duration of each program span in the window;
* ``idle_by_span``: device idle time of the window, split by the
  innermost span open at each instant, harness or program;
  ``queue_idle_share`` is the part under a ``queue.*`` span other than
  ``queue.infer``, as a percentage of the window;
* ``idle_gaps``: the longest idle gaps, labelled like ``devtrace``'s but
  with the innermost span of either kind;
* ``layer_ms``: per layer scope, the median over the window's plan calls
  of the device time of the operations inside the call's module event
  (containment on the device clock) that the plan's map puts there;
  ``unmapped`` collects the rest; ``scope_ms`` sums scopes per call
  (every ``pool*`` scope, say) before taking the median.

A trace from a program without these spans or modules gives empty
readings.  The window, busy time and kernel join stay ``devtrace``'s.
"""

from __future__ import annotations

import bisect
import dataclasses
import pathlib
import re
from typing import Dict, List, Optional

import numpy as np

import devtrace

PROGRAM_PREFIXES = ("queue.", "exe.", "plan.")
MODULES_LINE = "XLA Modules"
PLAN_MODULE = re.compile(r"^jit_plan_b(\d+)\b")
QUEUE_HOST = "queue."
QUEUE_DEVICE = "queue.infer"


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: int      # ns
    end: int        # ns
    args: tuple = ()  # sorted (key, value) pairs


def load(path) -> dict:
    """``devtrace.load``'s device ops and harness spans, plus the
    program's spans and each device's module events."""
    from jax.profiler import ProfileData

    tr = devtrace.load(path)
    path = pathlib.Path(path)
    if path.is_dir():
        path = sorted(path.rglob("*.xplane.pb"))[-1]
    program: List[Span] = []
    modules: Dict[int, List[devtrace.Event]] = {}
    for plane in ProfileData.from_file(str(path)).planes:
        m = devtrace.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == MODULES_LINE:
                evs = modules.setdefault(int(m.group(1)), [])
                for e in line.events:
                    start = int(e.start_ns)
                    evs.append(devtrace.Event(e.name, start,
                                              start + int(e.duration_ns)))
            elif not m and plane.name.startswith("/host"):
                for e in line.events:
                    if e.name.startswith(PROGRAM_PREFIXES):
                        start = int(e.start_ns)
                        program.append(Span(
                            e.name, start, start + int(e.duration_ns),
                            tuple(sorted(dict(e.stats).items()))))
    program.sort(key=lambda s: (s.start, -s.end))
    for evs in modules.values():
        evs.sort(key=lambda e: e.start)
    tr["program"] = program
    tr["modules"] = modules
    return tr


def idle_split(busy, spans, lo: int, hi: int) -> Dict[str, int]:
    """Idle ns of ``[lo, hi)`` outside the merged ``busy`` intervals, by
    the name of the innermost span open there ("outside spans" where
    none is)."""
    bounds = sorted({t for s in spans for t in (s.start, s.end)
                     if lo < t < hi})
    out: Dict[str, int] = {}
    for g0, g1 in devtrace.gaps(busy, lo, hi):
        i = bisect.bisect_right(bounds, g0)
        j = bisect.bisect_left(bounds, g1)
        cuts = [g0] + bounds[i:j] + [g1]
        for a, b in zip(cuts, cuts[1:]):
            sp = devtrace.span_at(spans, (a + b) // 2)
            name = sp.name if sp else "outside spans"
            out[name] = out.get(name, 0) + (b - a)
    return out


def plan_calls(ops, modules, layer_maps, lo: int, hi: int):
    """Per ``jit_plan_b<bucket>`` module event inside ``[lo, hi]``: ns
    by layer scope of the device ops it contains (``unmapped`` for ops
    the bucket's map does not name)."""
    starts = [e.start for e in ops]
    out = []
    for m in modules:
        mm = PLAN_MODULE.match(m.name)
        if mm is None or m.start < lo or m.end > hi:
            continue
        names = layer_maps.get(int(mm.group(1)), {})
        by: Dict[str, int] = {}
        for e in ops[bisect.bisect_left(starts, m.start):
                     bisect.bisect_right(starts, m.end)]:
            if e.end <= m.end:
                scope = names.get(e.name, "unmapped")
                by[scope] = by.get(scope, 0) + (e.end - e.start)
        out.append(by)
    return out


def reduce(path, layer_maps: Optional[dict] = None,
           n_devices: int = 1) -> Optional[dict]:
    """The readings above from one trace; ``layer_maps`` maps a bucket
    to its plan's ``layer_map()``.  None without a device event or a
    harness span (as ``devtrace.reduce``)."""
    return summarize(load(path), layer_maps, n_devices)


def summarize(tr: dict, layer_maps: Optional[dict] = None,
              n_devices: int = 1) -> Optional[dict]:
    """:func:`reduce` of what :func:`load` returned."""
    host = tr["host"]
    devices = {d: evs for d, evs in tr["devices"].items() if d < n_devices}
    if not host or not any(devices.values()):
        return None
    lo = min(h.start for h in host)
    hi = max(h.end for h in host)
    window = hi - lo
    program = [s for s in tr["program"] if s.start >= lo and s.end <= hi]
    spans = sorted(list(host) + program, key=lambda s: (s.start, -s.end))

    durs: Dict[str, List[int]] = {}
    for s in program:
        durs.setdefault(s.name, []).append(s.end - s.start)
    idle: Dict[str, int] = {}
    labelled, calls = [], []
    for d, evs in devices.items():
        busy = devtrace.merge(devtrace.clip([(e.start, e.end) for e in evs],
                                            lo, hi))
        for k, v in idle_split(busy, spans, lo, hi).items():
            idle[k] = idle.get(k, 0) + v
        for g0, g1 in devtrace.gaps(busy, lo, hi):
            sp = devtrace.span_at(spans, (g0 + g1) // 2)
            labelled.append([sp.name if sp else "outside spans",
                             (g1 - g0) / 1e9])
        calls += plan_calls(evs, tr["modules"].get(d, []), layer_maps or {},
                            lo, hi)
    n = len(devices)
    labelled.sort(key=lambda g: -g[1])
    scopes = sorted({k for c in calls for k in c})
    queue_idle = sum(v for k, v in idle.items()
                     if k.startswith(QUEUE_HOST) and k != QUEUE_DEVICE)
    return {
        "window_s": window / 1e9,
        "span_ms": {k: float(np.median(v)) / 1e6 for k, v in durs.items()},
        "span_counts": {k: len(v) for k, v in durs.items()},
        "idle_by_span": {k: v / n / 1e9 for k, v in
                         sorted(idle.items(), key=lambda kv: -kv[1])},
        "queue_idle_share": 100.0 * queue_idle / n / window,
        "idle_gaps": labelled[:devtrace.TOP],
        "plan_calls": calls,
        "layer_ms": {k: float(np.median([c.get(k, 0) for c in calls])) / 1e6
                     for k in scopes},
    }


def scope_ms(red: Optional[dict], prefix: str) -> Optional[float]:
    """Median over the plan calls of the device ms in the layer scopes
    starting with ``prefix`` (``pool`` for every pooling layer,
    ``input``; ``""`` for the whole call); None where the trace has no
    plan call with such a scope."""
    calls = red["plan_calls"] if red else []
    if not any(k.startswith(prefix) for c in calls for k in c):
        return None
    return float(np.median([sum(v for k, v in c.items()
                                if k.startswith(prefix)) for c in calls])) / 1e6
