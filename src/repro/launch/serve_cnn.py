"""Batched SNN/CNN inference serving over compiled fused-kernel plans.

The paper deploys single images on the FPGA; the production twin has to
survive *traffic*: arbitrary request sizes arriving continuously.  This
driver stacks three layers (DESIGN.md §3):

1. **Compiled executable** (``repro.api.Accelerator.compile`` ->
   ``Executable``): plans pre-compiled for a bucket ladder; requests pad
   to the nearest bucket, so no request size ever recompiles on the hot
   path.
2. **Data-parallel plans**: each bucket's plan is ``shard_map``-ped over
   the batch axis across visible devices (weights replicated), with
   transparent single-device fallback.
3. **Micro-batching queue** (:class:`MicroBatchQueue`): requests collect
   until the batch is full or the oldest request times out, then flush as
   one plan call — amortizing dispatch without unbounded latency.

The target encoding is swappable from the CLI (``--encoding`` with
``--num-steps``/``--periods``; docs/encodings.md is the selection guide):
kernels-capable specs (radix, TTFS, phase) serve compiled kernel plans
with the sparsity-aware plane-occupancy schedule (docs/kernels.md —
``Executable.stats()`` reports the skipped plane passes), while the
jnp-only rate spec serves per-bucket jitted closures — same bucketing,
queueing and stats machinery either way.

The queue is fault-tolerant (docs/serving.md; policy objects in
``repro.runtime.resilience``): admissions are bounded with backpressure,
tickets carry deadlines and are shed once expired, a failing flush is
recovered by **bisecting quarantine** (a poison request is isolated in
O(log n) re-flushes and fails alone with a bounded retry budget while
healthy co-batched tickets complete), and a healthy → degraded →
draining health machine over per-flush latencies falls back to smaller
flush groups before refusing admissions.  Every shed/failed ticket
*resolves* with a typed terminal error; the ``rejected / shed / retried
/ quarantined / degraded_flushes`` counters ride along in
``server.stats()``.

Usage:
  python -m repro.launch.serve_cnn --arch vgg11 --smoke
  python -m repro.launch.serve_cnn --arch lenet5 --requests 64 --buckets 1,4,8
  python -m repro.launch.serve_cnn --arch lenet5 --smoke --dataflow bitserial
  python -m repro.launch.serve_cnn --arch lenet5 --smoke \\
      --encoding phase --num-steps 8 --periods 2
  python -m repro.launch.serve_cnn --arch fang_cnn --smoke \\
      --encoding ttfs --pool-mode avg --dataflow bitserial
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import math
import os
import sys
import time
from concurrent import futures
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.core import conversion, engine
from repro.launch import compile_cache
from repro.runtime import resilience
from repro.runtime.trace import span

__all__ = [
    "ARCHS",
    "ENCODINGS",
    "make_encoding",
    "build_qnet",
    "CNNServer",
    "MicroBatchQueue",
    "Ticket",
    "run_request_stream",
    "main",
]


# A staged group is copied in parts of at least PART_BYTES on up to POOL
# threads (numpy releases the GIL while it copies); a group under two
# parts' bytes is copied on the calling thread (docs/serving.md §8).
PART_BYTES = 4 << 20
POOL = min(8, len(os.sched_getaffinity(0)))
_copy_pool: Optional[futures.ThreadPoolExecutor] = None


def _assemble(reqs: Sequence[np.ndarray], batch: np.ndarray) -> int:
    """Copy ``reqs`` one after another along the batch axis into
    ``batch``; the number of parts the copy was split into.

    The parts split the item axis that is outermost in ``batch``'s
    memory order (H for C-ordered and for batch-minor batches), never
    the batch axis, so each part copies a slice of every request (into
    one contiguous range of a batch-minor batch).  Every part has
    finished when this returns, and the first part to fail raises."""
    axes = [a for a in range(1, batch.ndim) if batch.shape[a] > 1]
    axis = max(axes, key=lambda a: abs(batch.strides[a]), default=0)
    parts = min(POOL, batch.nbytes // PART_BYTES,
                batch.shape[axis]) if axis else 1
    if parts <= 1:
        np.concatenate(reqs, axis=0, out=batch)
        return 1
    cuts = [batch.shape[axis] * j // parts for j in range(parts + 1)]

    def part(j: int) -> None:
        at = (slice(None),) * axis + (slice(cuts[j], cuts[j + 1]),)
        np.concatenate([x[at] for x in reqs], axis=0, out=batch[at])

    global _copy_pool
    if _copy_pool is None:
        _copy_pool = futures.ThreadPoolExecutor(
            POOL, thread_name_prefix="assemble")
    running = [_copy_pool.submit(part, j) for j in range(parts)]
    futures.wait(running)
    for f in running:
        f.result()
    return parts


# CLI name -> spec constructor; phase is the only one with an extra knob
ENCODINGS = {
    "radix": api.RadixEncoding,
    "rate": api.RateEncoding,
    "ttfs": api.TTFSEncoding,
    "phase": api.PhaseEncoding,
}


def make_encoding(name: str, num_steps: int, *,
                  periods: int = 1) -> api.EncodingSpec:
    """Build an :class:`repro.api.EncodingSpec` from CLI-style arguments.

    ``periods`` only applies to phase coding; passing it with any other
    encoding raises (nothing silently ignored).
    """
    if name not in ENCODINGS:
        raise ValueError(
            f"encoding must be one of {sorted(ENCODINGS)}, got {name!r}")
    if name == "phase":
        return api.PhaseEncoding(num_steps, periods=periods)
    if periods != 1:
        raise ValueError(
            f"--periods applies to phase coding only, not {name!r}")
    return ENCODINGS[name](num_steps)


# ---------------------------------------------------------------------------
# Architecture registry (the paper's three CNNs).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ArchSpec:
    """``make()`` kwargs for the full config and the CPU smoke config.

    ``smoke``/``full`` are either kwargs dicts or the name of a dict
    attribute on ``module`` (resolved at :func:`build_qnet` time, keeping
    the registry import-lazy while presets live next to their model)."""

    module: str
    full: "dict | str" = dataclasses.field(default_factory=dict)
    smoke: "dict | str" = dataclasses.field(default_factory=dict)


ARCHS = {
    "lenet5": ArchSpec("repro.models.lenet",
                       smoke={"width_mult": 0.25}),
    "fang_cnn": ArchSpec("repro.models.fang",
                         smoke={"width_mult": 0.25}),
    "vgg11": ArchSpec("repro.models.vgg",
                      full={"input_hw": (224, 224, 3)},
                      smoke="SMOKE_KWARGS"),
}


def build_float_net(
    arch: str,
    *,
    smoke: bool = False,
    pool_mode: str = "or",
    calib_batch: int = 4,
    seed: int = 0,
):
    """(static, params, item shape, synthetic calibration batch) for an
    arch id — the pre-conversion float net, which is what the PPA
    planner needs (``--auto`` re-quantizes it once per candidate
    encoding)."""
    spec = ARCHS[arch.replace("-", "_")]
    maker = importlib.import_module(spec.module)
    preset = spec.smoke if smoke else spec.full
    if isinstance(preset, str):
        preset = getattr(maker, preset)
    kwargs = dict(preset)
    static, params, input_hw = maker.make(
        key=jax.random.PRNGKey(seed), pool_mode=pool_mode, **kwargs)
    rng = np.random.default_rng(seed)
    calib = jnp.asarray(rng.uniform(0, 1, (calib_batch,) + tuple(input_hw)),
                        jnp.float32)
    return static, params, tuple(input_hw), calib


def build_qnet(
    arch: str,
    *,
    smoke: bool = False,
    pool_mode: str = "or",
    num_steps: Optional[int] = None,
    encoding: Optional[api.EncodingSpec] = None,
    weight_bits: int = 3,
    calib_batch: int = 4,
    seed: int = 0,
) -> Tuple[conversion.QuantizedNet, Tuple[int, int, int]]:
    """(converted net, item shape) for an arch id, synthetic calibration.

    ``encoding`` selects the target spec (default: radix at ``num_steps``,
    itself defaulting to 4).  Both are forwarded to ``convert`` as given,
    so a contradicting (num_steps, encoding) pair fails loudly there."""
    if encoding is None and num_steps is None:
        num_steps = 4
    static, params, input_hw, calib = build_float_net(
        arch, smoke=smoke, pool_mode=pool_mode, calib_batch=calib_batch,
        seed=seed)
    qnet = conversion.convert(static, params, calib, num_steps=num_steps,
                              encoding=encoding, weight_bits=weight_bits)
    return qnet, input_hw


# ---------------------------------------------------------------------------
# Server: plan cache + request entry point.
# ---------------------------------------------------------------------------


class CNNServer:
    """One converted net behind a compiled :class:`repro.api.Executable`.

    The server owns no execution machinery of its own: batching buckets,
    plan caching, data-parallel sharding and the stats counters all live
    on the executable (``server.exe``).  The serving-resilience counters
    (``resilience``, a :class:`~repro.runtime.resilience.ResilienceStats`
    mutated by the server's :class:`MicroBatchQueue`) are attached to the
    executable's stats surface, so ``server.stats()`` reports
    rejected/shed/retried/quarantined/degraded_flushes next to the
    plan-cache counters."""

    def __init__(
        self,
        qnet: conversion.QuantizedNet,
        item_shape: Tuple[int, ...],
        *,
        buckets: Sequence[int] = engine.DEFAULT_BUCKETS,
        dataflow: Optional[str] = None,
        backend: str = "kernels",
        data_parallel: Optional[int] = None,
        executable: Optional[api.Executable] = None,
    ):
        self.qnet = qnet
        self.item_shape = tuple(item_shape)
        self.exe = executable if executable is not None else api.Accelerator(
            backend=backend, dataflow=dataflow,
        ).compile(qnet, self.item_shape, parallel=data_parallel,
                  buckets=buckets)
        self.resilience = resilience.ResilienceStats()
        self.exe.attach_stats(self.resilience.as_dict)

    def warmup(self) -> None:
        """Compile every bucket up front (serving never compiles again)."""
        self.exe.warmup()

    def stats(self) -> dict:
        return self.exe.stats()

    def infer(self, x) -> jax.Array:
        """(n,) + item_shape float images -> (n, classes) float logits.

        The executable moves ``x`` to the device (the ``exe.to_device``
        span), once."""
        shape = np.shape(x)
        if tuple(shape[1:]) != self.item_shape:
            raise ValueError(
                f"request item shape {tuple(shape[1:])} != server's "
                f"{self.item_shape}")
        return self.exe(x)


# ---------------------------------------------------------------------------
# Micro-batching request queue.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Ticket:
    """Handle returned by :meth:`MicroBatchQueue.submit`.

    A ticket always reaches a terminal state: either ``result`` holds
    the logits, or ``error`` holds a
    :class:`~repro.runtime.resilience.ServeError` (rejected at submit,
    shed on deadline, or quarantined as poisoned) — never a dangling
    ``result is None`` forever.  ``deadline`` is an absolute queue-clock
    time; expired tickets are shed before they reach a flush."""

    size: int
    t_submit: float
    deadline: Optional[float] = None      # absolute clock time; None = none
    id: int = 0                           # the queue's sequence number
    result: Optional[jax.Array] = None
    error: Optional[Exception] = None     # terminal ServeError
    latency_s: Optional[float] = None     # submit -> resolved (either way)

    @property
    def done(self) -> bool:
        """Terminal: resolved with logits OR a typed error."""
        return self.result is not None or self.error is not None

    @property
    def ok(self) -> bool:
        """Resolved successfully (logits available)."""
        return self.result is not None


ADMISSION_POLICIES = ("reject", "flush")


class MicroBatchQueue:
    """Fault-tolerant collect-until-full-or-timeout micro-batcher.

    Requests (single images or small batches) accumulate; the queue flushes
    as **one** batched ``server.infer`` call when either

    * the pending image count reaches ``max_batch`` (one top-bucket plan
      call, zero padding waste), or
    * the oldest pending request has waited ``timeout_s`` (bounded latency
      under trickle load — the batch pads up to its bucket instead).

    Hostile traffic is survived by policy, not luck (docs/serving.md;
    DESIGN.md §3 failure-mode table):

    * **Bounded admission** — ``pending_images`` never exceeds
      ``max_pending``.  An over-bound submit is *rejected* (the ticket
      resolves immediately with
      :class:`~repro.runtime.resilience.AdmissionError`) or, with
      ``admission="flush"``, the queue applies backpressure by flushing
      synchronously to make room first.
    * **Deadlines** — a ticket whose deadline passed is shed (resolves
      with :class:`~repro.runtime.resilience.DeadlineExceeded`) before
      it wastes a flush, and again mid-recovery: the retry path checks
      the deadline around every backoff, so an isolated failing ticket
      never burns retry budget — or resolves — after its caller stopped
      waiting.
    * **Bisecting quarantine** — a failing flush is split in half and the
      halves re-flushed, so one poisoned request is isolated in O(log n)
      re-flushes and fails alone (after a bounded
      :class:`~repro.runtime.resilience.RetryPolicy` backoff budget for
      transient faults) while every healthy co-batched ticket completes
      bit-exact; the poisoned ticket resolves with
      :class:`~repro.runtime.resilience.RequestPoisoned`.
    * **Health machine** — per-flush latencies feed a
      :class:`~repro.runtime.resilience.HealthMonitor` (StragglerMonitor
      median/MAD underneath).  A faulting flush group counts as exactly
      *one* unhealthy sample, no matter how many bisection sub-flushes
      and retries its recovery takes — one hostile request degrades the
      server but cannot alone escalate it to draining.  Degraded serving
      flushes in groups of at most ``degraded_max_batch`` images (a
      smaller bucket, which also shards over fewer devices); draining
      refuses admissions until ``health.resume()``.

    A group of several requests with at most ``max_batch`` images is
    assembled into a float32 staging buffer of ``max_batch`` images that
    the queue keeps across flushes (``staging_allocs`` counts the
    buffers allocated), so a large batch is not written into freshly
    faulted pages on every flush.  The batch takes the memory order of
    the group's requests, as a new concatenation would: C order for
    C-ordered requests, batch-minor for slices of an array that
    ``np.asarray`` made from a TPU array.  A single request goes to the
    server as it came; a larger group is concatenated into a new array.
    A staged copy of at least two ``PART_BYTES`` runs in parts on a
    thread pool (:func:`_assemble`; ``split_assemblies`` counts those
    groups).  The buffer's contract:

    * it is rewritten only after the previous ``server.infer`` on it has
      returned through ``jax.block_until_ready``;
    * every part of a split copy has finished before ``server.infer``
      is called; a part that raises is a fault of the group, as an
      infer fault is;
    * after any infer fault the queue drops it and allocates another:
      the failed call may still read it (a transfer in flight, or a
      zero-copy alias on CPU), and the bisection and retry sub-flushes
      must never write memory it may read;
    * tickets hold the logits the server returned, never views of the
      buffer, and a server keeps nothing of the batch past ``infer``.

    Single-threaded and event-driven: callers drive time via
    :meth:`submit` / :meth:`poll` (``clock`` and the backoff ``sleep``
    injectable, so chaos tests are deterministic).  Latency recorded per
    ticket spans the *original* submit -> resolved, through any retries
    — the number a serving SLO cares about.
    """

    def __init__(
        self,
        server: CNNServer,
        *,
        max_batch: Optional[int] = None,
        timeout_s: float = 0.005,
        clock: Callable[[], float] = time.monotonic,
        max_pending: Optional[int] = None,
        admission: str = "reject",
        default_deadline_s: Optional[float] = None,
        retry: Optional[resilience.RetryPolicy] = resilience.RetryPolicy(),
        health: Optional[resilience.HealthMonitor] = None,
        degraded_max_batch: Optional[int] = None,
        sleep: Callable[[float], None] = time.sleep,
    ):
        self.server = server
        self.max_batch = int(max_batch or server.exe.buckets[-1])
        self.timeout_s = float(timeout_s)
        self.clock = clock
        if admission not in ADMISSION_POLICIES:
            raise ValueError(
                f"admission must be one of {ADMISSION_POLICIES}, got "
                f"{admission!r}")
        self.admission = admission
        self.max_pending = int(max_pending if max_pending is not None
                               else 8 * self.max_batch)
        if self.max_pending < 1:
            raise ValueError(
                f"max_pending must be >= 1, got {self.max_pending}")
        self.default_deadline_s = default_deadline_s
        self.retry = retry
        self.health = health if health is not None \
            else resilience.HealthMonitor()
        if degraded_max_batch is None:
            smaller = [b for b in server.exe.buckets if b < self.max_batch]
            degraded_max_batch = smaller[-1] if smaller else self.max_batch
        self.degraded_max_batch = max(1, int(degraded_max_batch))
        self._sleep = sleep
        self.counters = getattr(server, "resilience", None)
        if self.counters is None:
            self.counters = resilience.ResilienceStats()
        self._pending: List[Tuple[np.ndarray, Ticket]] = []
        self._count = 0
        self.flushes = 0          # successful infer flushes (incl. halves)
        self._staging: Optional[np.ndarray] = None
        self.staging_allocs = 0   # staging buffers allocated for assembly
        self.split_assemblies = 0  # staged groups copied in several parts
        # ids for the trace spans; the last flush id is the running flush's
        self._tickets = 0
        self._flush_ids = 0

    @property
    def pending_images(self) -> int:
        return self._count

    def _reject(self, ticket: Ticket, reason: str) -> Ticket:
        ticket.error = resilience.AdmissionError(reason)
        ticket.latency_s = 0.0
        self.counters.rejected += 1
        return ticket

    def submit(self, x, *, deadline_s: Optional[float] = None) -> Ticket:
        """Enqueue one request (item or (n,)+item batch); may flush.

        Shape-validates here, not at flush time: a malformed request must
        fail its own submit (``ValueError`` — a caller bug, not a fault),
        never poison the co-batched tickets already queued.  Admission
        failures are *faults*, not bugs: the returned ticket resolves
        immediately with an
        :class:`~repro.runtime.resilience.AdmissionError` instead of
        raising.  ``deadline_s`` (default ``default_deadline_s``) is a
        relative deadline from now; expired tickets are shed pre-flush."""
        self._tickets += 1
        with span("queue.submit", ticket=self._tickets) as sp:
            x = np.asarray(x, np.float32)
            if x.ndim == len(self.server.item_shape):
                x = x[None]
            if tuple(x.shape[1:]) != self.server.item_shape:
                raise ValueError(
                    f"request item shape {tuple(x.shape[1:])} != server's "
                    f"{self.server.item_shape}")
            if x.shape[0] == 0:
                raise ValueError("empty request (0 images)")
            sp.set_metadata(images=x.shape[0])
            now = self.clock()
            if deadline_s is None:
                deadline_s = self.default_deadline_s
            ticket = Ticket(size=x.shape[0], t_submit=now,
                            deadline=None if deadline_s is None
                            else now + deadline_s, id=self._tickets)
            if not self.health.accepting:
                return self._reject(
                    ticket, f"server draining (health={self.health.state}"
                    "); not accepting new requests")
            if self._count + ticket.size > self.max_pending:
                if self.admission == "flush":
                    # backpressure: drain to make room
                    self._flush("backpressure")
                if self._count + ticket.size > self.max_pending:
                    return self._reject(
                        ticket, f"queue at admission bound: {self._count} "
                        f"pending + {ticket.size} > max_pending="
                        f"{self.max_pending}")
            self._pending.append((x, ticket))
            self._count += ticket.size
        self.poll(now)
        return ticket

    def _shed_if_expired(self, ticket: Ticket,
                         now: Optional[float] = None) -> bool:
        """Resolve ``ticket`` with ``DeadlineExceeded`` if its deadline
        passed; True if shed.  Applied both while queued (pre-flush) and
        mid-retry — a ticket must never burn backoff budget, or resolve,
        after the caller has stopped waiting."""
        now = self.clock() if now is None else now
        if ticket.deadline is None or now < ticket.deadline:
            return False
        ticket.error = resilience.DeadlineExceeded(
            f"deadline passed {now - ticket.deadline:.4f}s ago")
        ticket.latency_s = now - ticket.t_submit
        self.counters.shed += 1
        return True

    def _shed_expired(self, now: float) -> None:
        """Resolve-and-drop every pending ticket whose deadline passed."""
        if all(t.deadline is None for _, t in self._pending):
            return
        kept = []
        for x, ticket in self._pending:
            if self._shed_if_expired(ticket, now):
                self._count -= ticket.size
            else:
                kept.append((x, ticket))
        self._pending = kept

    def poll(self, now: Optional[float] = None) -> bool:
        """Shed expired tickets, then flush if full or the oldest request
        timed out; True if flushed."""
        now = self.clock() if now is None else now
        self._shed_expired(now)
        if not self._pending:
            return False
        oldest = self._pending[0][1].t_submit
        if self._count >= self.max_batch:
            self._flush("full")
            return True
        if now - oldest >= self.timeout_s:
            self._flush("timeout")
            return True
        return False

    def flush(self) -> None:
        """Run everything pending; every involved ticket reaches a
        terminal state (logits, shed, or quarantined) — flush itself
        never raises on an infer fault."""
        self._flush("explicit")

    def _flush(self, trigger: str) -> None:
        """:meth:`flush`, which ``trigger`` (``full``, ``timeout``,
        ``backpressure`` or ``explicit``) names in its ``queue.flush``
        span."""
        self._shed_expired(self.clock())
        if not self._pending:
            return
        pending, images = self._pending, self._count
        self._pending, self._count = [], 0
        self._flush_ids += 1
        with span("queue.flush", flush=self._flush_ids, tickets=len(pending),
                  first_ticket=pending[0][1].id,
                  last_ticket=pending[-1][1].id, images=images,
                  trigger=trigger):
            if self.health.degraded:
                groups = self._split(pending, self.degraded_max_batch)
            else:
                groups = [pending]
            for group in groups:
                if self._run_group(group):
                    # one fault event = ONE unhealthy health sample,
                    # however many bisection sub-flushes and retries it
                    # took to isolate — a single poisoned request must
                    # degrade the server, never single-handedly drive it
                    # to draining
                    self.health.record_failure()

    @staticmethod
    def _split(pending, cap: int):
        """Greedy FIFO grouping at <= cap images per group (a single
        request larger than cap keeps its own group — requests are never
        split)."""
        groups, cur, n = [], [], 0
        for x, ticket in pending:
            if cur and n + ticket.size > cap:
                groups.append(cur)
                cur, n = [], 0
            cur.append((x, ticket))
            n += ticket.size
        if cur:
            groups.append(cur)
        return groups

    def _run_group(self, group) -> bool:
        """One batched infer over ``group``; on failure, bisect (multi-
        ticket) or retry-then-quarantine (single ticket); a failed copy
        into the staging buffer is a fault like a failed infer.  Returns
        True if any attempt in the subtree faulted — the *caller*
        (``flush``) records at most one health failure per top-level
        group, not one per bisection level or retry attempt."""
        rows = sum(x.shape[0] for x, _ in group)
        staged = len(group) > 1 and rows <= self.max_batch
        if self.health.degraded:
            self.counters.degraded_flushes += 1
        try:
            with span("queue.assemble", flush=self._flush_ids,
                      bytes=sum(x.nbytes for x, _ in group),
                      staged=int(staged)) as sp:
                parts = 0
                if len(group) == 1:
                    batch = group[0][0]
                elif staged:
                    batch = self._staging_batch(group, rows)
                    parts = _assemble([x for x, _ in group], batch)
                    self.split_assemblies += parts > 1
                else:
                    batch = np.concatenate([x for x, _ in group], axis=0)
                sp.set_metadata(parts=parts)
            t0 = self.clock()
            with span("queue.infer", flush=self._flush_ids,
                      images=batch.shape[0]):
                logits = self.server.infer(batch)
                jax.block_until_ready(logits)
        except Exception as err:
            # the failed call may still read the buffer (a transfer in
            # flight, or a zero-copy alias on CPU): never write it again;
            # a failed copy drops it too, as any fault of the group does
            self._staging = None
            if len(group) > 1:
                # bisecting quarantine: O(log n) re-flushes isolate one
                # poison request; healthy halves complete on their own
                mid = len(group) // 2
                self._run_group(group[:mid])
                self._run_group(group[mid:])
                return True
            self._retry_single(group[0], err)
            return True
        self._resolve(group, logits, t0)
        return False

    def _staging_batch(self, group, rows: int) -> np.ndarray:
        """A ``(rows,) + item_shape`` batch at the head of the staging
        buffer (allocated on first use and again after a fault, and kept
        across flushes), laid out in memory as the group's first request
        of more than one image is: the layout ``np.concatenate`` gives a
        new batch, so the copy runs in memory order and the server gets
        the layout it got before.  C order when every request is one
        image, whose batch axis says nothing."""
        if self._staging is None:
            self._staging = np.empty(
                self.max_batch * math.prod(self.server.item_shape),
                np.float32)
            self.staging_allocs += 1
        shape = (rows,) + self.server.item_shape
        order = list(range(len(shape)))
        x = next((x for x, _ in group if x.shape[0] > 1), None)
        if x is not None:
            # axes outermost first by stride; an axis of length 1 keeps
            # its place, its stride being arbitrary
            by_stride = iter(sorted((a for a in order if x.shape[a] > 1),
                                    key=lambda a: -abs(x.strides[a])))
            order = [a if x.shape[a] == 1 else next(by_stride)
                     for a in order]
        return self._staging[:math.prod(shape)].reshape(
            [shape[a] for a in order]).transpose(np.argsort(order))

    def _retry_single(self, item, err: Exception) -> None:
        """Bounded backoff retries for an isolated ticket; shed the
        moment the ticket's deadline passes (before *and* after each
        backoff — a retry must not resolve work the caller stopped
        waiting for), quarantine on an exhausted budget."""
        x, ticket = item
        budget = self.retry.max_retries if self.retry is not None else 0
        for attempt in range(budget):
            if self._shed_if_expired(ticket):
                return
            self.counters.retried += 1
            self._sleep(self.retry.backoff(attempt))
            if self._shed_if_expired(ticket):
                return
            t0 = self.clock()
            try:
                with span("queue.infer", flush=self._flush_ids,
                          images=ticket.size):
                    logits = self.server.infer(x)
                    jax.block_until_ready(logits)
            except Exception as again:
                err = again
                continue
            self._resolve([item], logits, t0)
            return
        poisoned = resilience.RequestPoisoned(
            f"request of {ticket.size} image(s) failed alone after "
            f"{budget} retries: {err}")
        poisoned.__cause__ = err
        ticket.error = poisoned
        ticket.latency_s = self.clock() - ticket.t_submit
        self.counters.quarantined += 1

    def _resolve(self, group, logits, t0: float) -> None:
        with span("queue.resolve", flush=self._flush_ids,
                  tickets=len(group)):
            done = self.clock()
            self.flushes += 1
            self.health.record_flush(done - t0)
            off = 0
            for x, ticket in group:
                ticket.result = logits[off:off + x.shape[0]]
                ticket.latency_s = done - ticket.t_submit
                off += x.shape[0]


# ---------------------------------------------------------------------------
# Request-stream driver (CLI + benchmarks/serve_bench.py).
# ---------------------------------------------------------------------------


def run_request_stream(
    queue: MicroBatchQueue,
    sizes: Sequence[int],
    *,
    seed: int = 0,
    drain: bool = True,
    deadline_s: Optional[float] = None,
) -> List[Ticket]:
    """Submit a stream of random requests of the given sizes; returns the
    tickets (drains the queue at the end, so every ticket is terminal —
    resolved, shed, rejected or quarantined)."""
    rng = np.random.default_rng(seed)
    item = queue.server.item_shape
    tickets = [queue.submit(rng.uniform(0, 1, (int(n),) + item)
                            .astype(np.float32), deadline_s=deadline_s)
               for n in sizes]
    if drain:
        queue.flush()
    return tickets


def _percentiles(latencies_ms: Sequence[float]) -> Tuple[float, float]:
    return (float(np.percentile(latencies_ms, 50)),
            float(np.percentile(latencies_ms, 95)))


def _parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    """Parse + *loudly* validate CLI args (``argparse.ArgumentParser
    .error`` -> exit 2).  Silent acceptance of a negative timeout, a
    non-positive request count, or an unsorted/duplicate bucket ladder
    used to produce confusing downstream behavior; every constraint now
    fails at the CLI boundary with the offending value named.  The
    validated bucket ladder is returned as ``args.bucket_ladder``."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--pool-mode", default="or", choices=["or", "avg", "max"],
                    help="rate needs avg; ttfs needs avg/max (the spec "
                         "validates loudly)")
    ap.add_argument("--num-steps", type=int, default=None,
                    help="total time steps T, default 4 (phase: all "
                         "periods)")
    ap.add_argument("--encoding", default=None, choices=sorted(ENCODINGS),
                    help="target neural encoding (docs/encodings.md); "
                         "default radix")
    ap.add_argument("--periods", type=int, default=None,
                    help="phase coding: repeated periods P (T/P phases); "
                         "default 1")
    ap.add_argument("--backend", default=None, choices=["kernels", "jnp"],
                    help="default: kernels when the encoding supports it, "
                         "else jnp")
    ap.add_argument("--buckets", default="1,8,32",
                    help="comma-separated batch bucket ladder (strictly "
                         "ascending positive ints)")
    ap.add_argument("--dataflow", default=None,
                    choices=["fused", "bitserial"],
                    help="in-kernel dataflow (kernels backend; default: "
                         "the encoding's first declared dataflow)")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--max-request", type=int, default=8,
                    help="request sizes drawn uniformly from [1, this]")
    ap.add_argument("--timeout-ms", type=float, default=2.0)
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline; expired tickets are shed "
                         "with DeadlineExceeded (docs/serving.md)")
    ap.add_argument("--max-pending", type=int, default=None,
                    help="admission bound on pending images (default "
                         "8 x max batch)")
    ap.add_argument("--admission", default="reject",
                    choices=sorted(ADMISSION_POLICIES),
                    help="over-bound submits: reject with AdmissionError, "
                         "or flush (synchronous backpressure)")
    ap.add_argument("--retries", type=int, default=2,
                    help="retry budget for an isolated failing request "
                         "before quarantine")
    ap.add_argument("--data-parallel", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--auto", action="store_true",
                    help="let the PPA planner pick encoding/T/dataflow/"
                         "units under the constraints below (docs/ppa.md)")
    ap.add_argument("--accuracy-floor", type=float, default=None,
                    help="--auto: minimum calibration-batch fidelity vs "
                         "the float reference (default 0.9)")
    ap.add_argument("--latency-slo", type=float, default=None,
                    help="--auto: modeled per-image latency ceiling (us)")
    ap.add_argument("--energy-budget", type=float, default=None,
                    help="--auto: modeled per-image energy ceiling (uJ)")
    args = ap.parse_args(argv)

    if args.auto:
        for flag, val in (("--encoding", args.encoding),
                          ("--dataflow", args.dataflow),
                          ("--backend", args.backend),
                          ("--num-steps", args.num_steps),
                          ("--periods", args.periods)):
            if val is not None:
                ap.error(f"{flag} conflicts with --auto (the planner "
                         "owns that axis)")
        if args.accuracy_floor is None:
            args.accuracy_floor = 0.9
        if not 0.0 < args.accuracy_floor <= 1.0:
            ap.error(f"--accuracy-floor must be in (0, 1], got "
                     f"{args.accuracy_floor}")
        if args.latency_slo is not None and args.latency_slo <= 0:
            ap.error(f"--latency-slo must be positive, got "
                     f"{args.latency_slo}")
        if args.energy_budget is not None and args.energy_budget <= 0:
            ap.error(f"--energy-budget must be positive, got "
                     f"{args.energy_budget}")
    else:
        for flag, val in (("--accuracy-floor", args.accuracy_floor),
                          ("--latency-slo", args.latency_slo),
                          ("--energy-budget", args.energy_budget)):
            if val is not None:
                ap.error(f"{flag} is a planner constraint and requires "
                         "--auto")
    if args.encoding is None:
        args.encoding = "radix"
    if args.num_steps is None:
        args.num_steps = 4
    if args.periods is None:
        args.periods = 1

    if args.num_steps <= 0:
        ap.error(f"--num-steps must be positive, got {args.num_steps}")
    if args.requests <= 0:
        ap.error(f"--requests must be positive, got {args.requests}")
    if args.max_request <= 0:
        ap.error(f"--max-request must be positive, got {args.max_request}")
    if args.timeout_ms < 0:
        ap.error(f"--timeout-ms must be >= 0, got {args.timeout_ms}")
    if args.deadline_ms is not None and args.deadline_ms <= 0:
        ap.error(f"--deadline-ms must be positive, got {args.deadline_ms}")
    if args.max_pending is not None and args.max_pending < 1:
        ap.error(f"--max-pending must be >= 1, got {args.max_pending}")
    if args.retries < 0:
        ap.error(f"--retries must be >= 0, got {args.retries}")
    if args.data_parallel is not None and args.data_parallel < 1:
        ap.error(
            f"--data-parallel must be >= 1, got {args.data_parallel}")
    try:
        buckets = tuple(int(b) for b in args.buckets.split(","))
    except ValueError:
        ap.error(f"--buckets must be comma-separated ints, got "
                 f"{args.buckets!r}")
    if not buckets or any(b < 1 for b in buckets) or \
            list(buckets) != sorted(set(buckets)):
        ap.error("--buckets must be strictly ascending positive ints "
                 f"(no duplicates), got {args.buckets!r}")
    args.bucket_ladder = buckets
    return args


def main(argv: Optional[Sequence[str]] = None) -> int:
    """Serve one synthetic request stream; returns the exit status — 1
    when any ticket did not resolve with logits, else 0."""
    args = _parse_args(argv)
    buckets = args.bucket_ladder
    cache_dir = compile_cache.enable()
    if args.auto:
        static, params, item, calib = build_float_net(
            args.arch, smoke=args.smoke, pool_mode=args.pool_mode,
            calib_batch=64, seed=args.seed)
        plan = api.autoconfigure(
            (static, params), item, calib=calib,
            accuracy_floor=args.accuracy_floor,
            latency_slo_us=args.latency_slo,
            energy_budget_uj=args.energy_budget)
        print("[serve_cnn] " + plan.summary().replace("\n", "\n[serve_cnn] "))
        exe = plan.compile(buckets=buckets, parallel=args.data_parallel)
        server = CNNServer(exe.qnet, item, executable=exe)
        spec, backend = exe.encoding, exe.backend
    else:
        spec = make_encoding(args.encoding, args.num_steps,
                             periods=args.periods)
        backend = args.backend or ("kernels" if "kernels" in spec.backends
                                   else "jnp")
        qnet, item = build_qnet(args.arch, smoke=args.smoke,
                                pool_mode=args.pool_mode,
                                encoding=spec, seed=args.seed)
        server = CNNServer(qnet, item, buckets=buckets, backend=backend,
                           dataflow=args.dataflow,
                           data_parallel=args.data_parallel)
    dev = jax.devices()[0]
    print(f"[serve_cnn] {args.arch} {spec} backend={backend} item={item} "
          f"buckets={buckets} platform={dev.platform} "
          f"device_kind={dev.device_kind} devices={len(jax.devices())} "
          f"compile_cache={cache_dir}")
    t0 = time.monotonic()
    server.warmup()
    print(f"[serve_cnn] warmed {len(buckets)} bucket plans in "
          f"{time.monotonic() - t0:.1f}s; "
          f"compiles={server.stats()['compiles']}")

    queue = MicroBatchQueue(
        server, timeout_s=args.timeout_ms / 1e3,
        max_pending=args.max_pending, admission=args.admission,
        default_deadline_s=None if args.deadline_ms is None
        else args.deadline_ms / 1e3,
        retry=resilience.RetryPolicy(max_retries=args.retries))
    rng = np.random.default_rng(args.seed)
    sizes = rng.integers(1, args.max_request + 1, args.requests)
    t0 = time.monotonic()
    tickets = run_request_stream(queue, sizes, seed=args.seed)
    wall = time.monotonic() - t0
    ok = [t for t in tickets if t.ok]
    lat = [t.latency_s * 1e3 for t in ok]
    p50, p95 = _percentiles(lat) if lat else (float("nan"), float("nan"))
    images = int(sum(t.size for t in ok))
    stats = server.stats()
    print(f"[serve_cnn] {len(tickets)} requests / {images} images served in "
          f"{wall:.2f}s -> {images / wall:.1f} img/s; "
          f"latency p50={p50:.1f}ms p95={p95:.1f}ms")
    print(f"[serve_cnn] cache: hits={stats['hits']} "
          f"compiles={stats['compiles']} (steady-state recompiles="
          f"{stats['compiles'] - len(server.exe.buckets)}) "
          f"padded_rows={stats['padded_rows']} flushes={queue.flushes}")
    print(f"[serve_cnn] resilience: health={queue.health.state} "
          f"rejected={stats['rejected']} shed={stats['shed']} "
          f"retried={stats['retried']} quarantined={stats['quarantined']} "
          f"degraded_flushes={stats['degraded_flushes']} "
          f"failures={stats['failures']}")
    if len(ok) < len(tickets):
        print(f"[serve_cnn] FAILED: {len(tickets) - len(ok)} of "
              f"{len(tickets)} requests did not resolve with logits",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
