"""Program spans on the profiler's clock.

:func:`span` wraps ``jax.profiler.TraceAnnotation``: a span records into
the running ``jax.profiler`` session, on the same timeline as the
device's operations, and is the annotation's inactive path (about a
microsecond) when no session runs.  There is no flag: start a trace to
see the spans (docs/serving.md, "Tracing a server").

:data:`SPANS` lists every span the served path opens, with the arguments
each carries.  Arguments are ints or short strings the code already
has; nothing is formatted or read from a device to fill a span.  The
spans of one request share an identifier: ``queue.submit`` carries its
``ticket`` id, every ``queue.*`` span of a flush carries the ``flush``
id, and ``queue.flush`` names the first and last ticket it took (the
queue is FIFO, so the range names every ticket of the flush).
"""

from __future__ import annotations

import jax

__all__ = ["SPANS", "span", "read"]

# span name -> the arguments it carries, along the served path
# MicroBatchQueue -> CNNServer / api.Executable -> PlanCache -> CompiledPlan;
# a flush's ``trigger`` is "full", "timeout", "backpressure" or "explicit";
# ``staged`` is 1 when a group went into the queue's kept staging buffer,
# and ``parts`` is the number of parts that copy was split into (1: copied
# on the calling thread; 0: not staged)
SPANS = {
    "queue.submit": ("ticket", "images"),
    "queue.flush": ("flush", "tickets", "first_ticket", "last_ticket",
                    "images", "trigger"),
    "queue.assemble": ("flush", "bytes", "staged", "parts"),
    "queue.infer": ("flush", "images"),
    "queue.resolve": ("flush", "tickets"),
    "exe.to_device": ("images", "bytes"),
    "exe.pad": ("bucket", "rows"),
    "plan.dispatch": ("bucket",),
}


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A context manager recording ``name`` with ``args`` while a
    profiler session runs, and nothing otherwise."""
    return jax.profiler.TraceAnnotation(name, **args)


def read(path) -> list:
    """The program's spans in the ``.xplane.pb`` file ``path``, or in the
    newest one under the directory ``path``: ``(name, start_ns, end_ns,
    args)`` tuples in start order, on the trace's clock."""
    import pathlib

    from jax.profiler import ProfileData

    path = pathlib.Path(path)
    if path.is_dir():
        files = sorted(path.rglob("*.xplane.pb"))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = files[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in SPANS:
                    start = int(e.start_ns)
                    out.append((e.name, start, start + int(e.duration_ns),
                                dict(e.stats)))
    out.sort(key=lambda s: (s[1], -s[2]))
    return out
