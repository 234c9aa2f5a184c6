"""Program spans on the profiler's clock (``repro.runtime.trace``).

A tiny LeNet-5 is served through a ``MicroBatchQueue`` under a CPU
``jax.profiler`` session: one flush that fills the batch and one that
times out (padded up to its bucket).  The trace, read back from its
``.xplane.pb``, must hold every span of ``trace.SPANS`` with its
arguments, nested along the served path, with the ticket and flush ids
agreeing.  Without a session the same run gives the same logits and
records nothing.  The plan's plane-skip counter must keep its counts
without a device program of its own per call.
"""

import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import conversion, engine
from repro.launch import serve_cnn
from repro.models import lenet
from repro.runtime import trace

MAX_BATCH = 8


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@pytest.fixture(scope="module")
def server():
    static, params, hw = lenet.make(pool_mode="or", width_mult=0.25)
    rng = np.random.default_rng(3)
    calib = jnp.asarray(rng.uniform(0, 1, (4,) + hw), jnp.float32)
    qnet = conversion.convert(static, params, calib, num_steps=4)
    # one device: a data-parallel plan also reshards its input per call
    srv = serve_cnn.CNNServer(qnet, hw, buckets=(4, MAX_BATCH),
                              data_parallel=1)
    srv.warmup()
    return srv


def _serve(server):
    """One full flush (two 4-image requests) and one timeout flush (one
    2-image request, padded to bucket 4); the tickets' logits."""
    rng = np.random.default_rng(7)
    clock = FakeClock()
    q = serve_cnn.MicroBatchQueue(server, max_batch=MAX_BATCH,
                                  timeout_s=0.01, clock=clock)
    tickets = [q.submit(rng.uniform(0, 1, (n,) + server.item_shape)
                        .astype(np.float32)) for n in (4, 4, 2)]
    clock.t = 1.0
    assert q.poll()
    assert all(t.ok for t in tickets)
    return [np.asarray(t.result) for t in tickets]


def _traced(server, tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        logits = _serve(server)
    finally:
        jax.profiler.stop_trace()
    return logits, trace.read(tmp_path)


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_spans_follow_the_served_path(server, tmp_path):
    _, spans = _traced(server, tmp_path)
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    assert set(by) == set(trace.SPANS)
    for name, args in trace.SPANS.items():
        for s in by[name]:
            assert set(args) <= set(s[3]), (name, s[3])

    submits = by["queue.submit"]
    assert [s[3]["ticket"] for s in submits] == [1, 2, 3]
    assert [s[3]["images"] for s in submits] == [4, 4, 2]

    flushes = by["queue.flush"]
    assert [(f[3]["flush"], f[3]["trigger"], f[3]["tickets"],
             f[3]["first_ticket"], f[3]["last_ticket"], f[3]["images"])
            for f in flushes] == [(1, "full", 2, 1, 2, 8),
                                  (2, "timeout", 1, 3, 3, 2)]
    for name in ("queue.assemble", "queue.infer", "queue.resolve"):
        assert [s[3]["flush"] for s in by[name]] == [1, 2]
        for s, f in zip(by[name], flushes):
            assert _inside(s, f), name
    # a flush is not part of the submit span: admission ends first
    for f in flushes:
        assert not any(_inside(f, s) for s in submits)

    item_bytes = 4 * int(np.prod(server.item_shape))
    assert [s[3]["bytes"] for s in by["queue.assemble"]] == [
        8 * item_bytes, 2 * item_bytes]
    # two requests go into the kept buffer; a lone one passes as it came
    assert [s[3]["staged"] for s in by["queue.assemble"]] == [1, 0]
    assert [s[3]["images"] for s in by["queue.infer"]] == [8, 2]
    assert [s[3]["tickets"] for s in by["queue.resolve"]] == [2, 1]

    infers = by["queue.infer"]
    for name in ("exe.to_device", "plan.dispatch"):
        assert len(by[name]) == 2
        for s, inf in zip(by[name], infers):
            assert _inside(s, inf), name
    assert [(s[3]["images"], s[3]["bytes"]) for s in by["exe.to_device"]] \
        == [(8, 8 * item_bytes), (2, 2 * item_bytes)]
    assert [s[3]["bucket"] for s in by["plan.dispatch"]] == [8, 4]
    # only the 2-image flush pads: 2 rows up to bucket 4
    (pad,) = by["exe.pad"]
    assert (pad[3]["bucket"], pad[3]["rows"]) == (4, 2)
    assert _inside(pad, infers[1])


def test_assemble_span_says_whether_the_group_was_staged(server, tmp_path,
                                                         monkeypatch):
    """``staged`` is 1 for a multi-request group that fits max_batch and
    0 for one wider than it, which is concatenated into a new array.
    ``parts`` is 1 for a staged group copied on the calling thread, the
    number of parts for one copied on the thread pool (here with parts
    of 8 KiB), and 0 for a group that was not staged."""
    rng = np.random.default_rng(5)
    q = serve_cnn.MicroBatchQueue(server, max_batch=MAX_BATCH,
                                  timeout_s=1e9, clock=FakeClock())
    monkeypatch.setattr(serve_cnn, "POOL", 8)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for sizes in ((3, 5), (4, 6), (2, 6)):    # 8, 10, then 8 rows
            if sizes == (2, 6):
                monkeypatch.setattr(serve_cnn, "PART_BYTES", 8 << 10)
            tickets = [q.submit(rng.uniform(0, 1, (n,) + server.item_shape)
                                .astype(np.float32)) for n in sizes]
            assert all(t.ok for t in tickets)
        q.submit(np.zeros((3,) + server.item_shape, np.float32))
        q.flush()                                 # a lone request
    finally:
        jax.profiler.stop_trace()
    assembles = [s for s in trace.read(tmp_path) if s[0] == "queue.assemble"]
    assert [(s[3]["staged"], s[3]["flush"]) for s in assembles] == [
        (1, 1), (0, 2), (1, 3), (0, 4)]
    assert [s[3]["parts"] for s in assembles] == [1, 0, 4, 0]
    assert q.staging_allocs == 1 and q.split_assemblies == 1


def test_no_session_same_logits_and_nothing_recorded(server, tmp_path):
    traced, _ = _traced(server, tmp_path / "on")
    plain = _serve(server)
    for a, b in zip(traced, plain):
        np.testing.assert_array_equal(a, b)
    # spans opened outside a session leave nothing for the next one
    jax.profiler.start_trace(str(tmp_path / "after"))
    jax.profiler.stop_trace()
    assert trace.read(tmp_path / "after") == []


@pytest.mark.parametrize("fold", [engine.SKIP_FOLD, 4])
def test_plane_counts_without_a_device_program_per_call(server, tmp_path,
                                                        monkeypatch, fold):
    """The skip counts of 50 calls equal the per-call counts summed on
    the host, with the per-call list bounded by the fold, and each call
    runs the plan's program and no other."""
    monkeypatch.setattr(engine, "SKIP_FOLD", fold)
    plan = server.exe.plan_for(4)
    rng = np.random.default_rng(11)
    xs = [jnp.asarray(rng.uniform(0, 1, (4,) + server.item_shape)
                      * (i % 3 != 0), jnp.float32) for i in range(50)]
    want = sum(int(np.asarray(plan._fn(plan._params, x)[1]).sum())
               for x in xs)
    jax.block_until_ready(xs)
    plan.reset_plane_stats()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for x in xs:
            plan(x)
            assert len(plan._skips) < 2 * fold
    finally:
        jax.profiler.stop_trace()
    assert plan.plane_stats() == {
        "plane_passes_skipped": want,
        "plane_passes_total": 50 * plan.plane_passes_per_call}
    assert 0 < want < 50 * plan.plane_passes_per_call

    path = sorted(pathlib.Path(tmp_path).rglob("*.xplane.pb"))[-1]
    names = [e.name for p in ProfileData.from_file(str(path)).planes
             for line in p.lines for e in line.events]
    assert {n for n in names if n.startswith("PjitFunction(")} == {
        "PjitFunction(plan_b4)"}
    assert names.count("PjRtCpuExecutable::Execute") == 50


def test_layer_map_names_every_layer(server):
    """Every layer scope of the plan that computes names some instruction
    of its compiled program (here the CPU's, kernels interpreted); the
    flatten is a free reshape."""
    scopes = set(server.exe.plan_for(4).layer_map().values())
    assert scopes == {"input", "conv1", "pool1", "conv2", "pool2", "conv3",
                      "fc1", "fc2", "fc3", "output"}


def test_layer_scopes_reads_op_name_metadata():
    text = "\n".join([
        'HloModule jit_plan_b8',
        'ENTRY %main {',
        '  %radix_conv2d_pallas.3 = u8[8,28,28,8] custom-call(%a), '
        'metadata={op_name="jit(plan_b8)/conv1/jit(radix_conv2d_pallas)/'
        'pallas_call" source_file="x.py"}',
        '  ROOT %mul.1 = f32[8,10] multiply(%b, %c), '
        'metadata={op_name="jit(plan_b8)/output/mul"}',
        '  %copy-start = (s8[5]) copy-start(%p)',
        '  %copy.13 = s8[5] copy(%q), metadata={op_name="params[0][\'w\']"}',
        '  %fusion.2 = s32[8] fusion(%r), metadata={op_name='
        '"jit(plan_b8)/jit(shmap_body)/jit(plan_b2)/pool12/reduce_window"}',
        '}'])
    assert engine.layer_scopes(text) == {
        "radix_conv2d_pallas.3": "conv1", "mul.1": "output",
        "fusion.2": "pool12"}
