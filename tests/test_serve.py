"""Serving stack: micro-batch queue + server + bench (DESIGN.md §3).

Pins the serving contract end to end: the queue flushes on full or on
timeout (deterministic via an injected clock), results are bit-exact per
request against the jnp engine path, mixed-size request streams hit
pre-compiled buckets with zero steady-state recompiles, and the bench
emits a well-formed BENCH_serve.json.
"""

import json
from concurrent.futures import ThreadPoolExecutor

import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import conversion
from repro.launch import serve_cnn
from repro.models import lenet

RNG = np.random.default_rng(5)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


@pytest.fixture(scope="module")
def server():
    static, params, input_hw = lenet.make(pool_mode="or", width_mult=0.25)
    calib = jnp.asarray(RNG.uniform(0, 1, (4,) + input_hw), jnp.float32)
    qnet = conversion.convert(static, params, calib, num_steps=4)
    srv = serve_cnn.CNNServer(qnet, input_hw, buckets=(1, 4, 8))
    srv.warmup()
    return srv


def _req(server, n):
    return RNG.uniform(0, 1, (n,) + server.item_shape).astype(np.float32)


# ---------------------------------------------------------------------------
# Micro-batch queue semantics (deterministic fake clock).
# ---------------------------------------------------------------------------


def test_queue_flushes_when_full(server):
    clock = FakeClock()
    q = serve_cnn.MicroBatchQueue(server, max_batch=4, timeout_s=1e9,
                                  clock=clock)
    t1 = q.submit(_req(server, 2))
    assert not t1.done and q.pending_images == 2
    t2 = q.submit(_req(server, 2))               # reaches max_batch -> flush
    assert t1.done and t2.done and q.pending_images == 0
    assert q.flushes == 1


def test_queue_flushes_on_timeout(server):
    clock = FakeClock()
    q = serve_cnn.MicroBatchQueue(server, max_batch=64, timeout_s=0.010,
                                  clock=clock)
    t1 = q.submit(_req(server, 1))
    clock.advance(0.005)
    assert not q.poll()                          # under timeout: holds
    clock.advance(0.006)
    assert q.poll()                              # oldest waited 11ms > 10ms
    assert t1.done and t1.latency_s == pytest.approx(0.011)


def test_queue_single_image_requests_get_batch_dim(server):
    q = serve_cnn.MicroBatchQueue(server, max_batch=2, timeout_s=1e9)
    t = q.submit(_req(server, 1)[0])             # item-shaped, no batch dim
    q.flush()
    assert t.size == 1 and t.result.shape[0] == 1


def test_queue_results_bit_exact_per_request(server):
    clock = FakeClock()
    q = serve_cnn.MicroBatchQueue(server, max_batch=16, timeout_s=1e9,
                                  clock=clock)
    reqs = [_req(server, n) for n in (3, 1, 5, 2)]
    tickets = [q.submit(r) for r in reqs]
    q.flush()
    for r, t in zip(reqs, tickets):
        ref = api.oracle(server.qnet, jnp.asarray(r), mode="packed")
        np.testing.assert_array_equal(np.asarray(t.result), np.asarray(ref))


# ---------------------------------------------------------------------------
# Flush assembly: the staging buffer kept across flushes.
# ---------------------------------------------------------------------------


class SpyServer:
    """The module's server, keeping each batch ``infer`` was given."""

    def __init__(self, server):
        self.server = server
        self.item_shape = server.item_shape
        self.exe = server.exe
        self.resilience = server.resilience
        self.batches = []

    def infer(self, x):
        self.batches.append(x)
        return self.server.infer(x)


def _fresh_logits(server, reqs):
    """Each request's rows of ``server.infer`` on a new concatenation."""
    out = np.asarray(server.infer(np.concatenate(reqs, axis=0)))
    offs = np.cumsum([0] + [r.shape[0] for r in reqs])
    return [out[a:b] for a, b in zip(offs[:-1], offs[1:])]


def test_full_flushes_reuse_one_staging_buffer_bit_exact(server):
    clock = FakeClock()
    spy = SpyServer(server)
    q = serve_cnn.MicroBatchQueue(spy, max_batch=8, timeout_s=1e9,
                                  clock=clock)
    flushes = []
    for sizes in ((3, 5), (2, 2, 4), (1, 6, 1), (4, 4)):
        reqs = [_req(server, n) for n in sizes]
        tickets = [q.submit(r) for r in reqs]     # the last fills -> flush
        assert all(t.ok for t in tickets)
        got = [np.asarray(t.result).copy() for t in tickets]
        for g, want in zip(got, _fresh_logits(server, reqs)):
            np.testing.assert_array_equal(g, want)
        flushes.append((tickets, got))
    assert q.flushes == 4 and q.staging_allocs == 1
    # every batch was a C-ordered view of the one kept buffer
    base = q._staging
    assert all(b.base is base and b.flags.c_contiguous
               for b in spy.batches)
    # later flushes overwrote the buffer; earlier tickets kept their rows
    for tickets, got in flushes:
        for t, g in zip(tickets, got):
            np.testing.assert_array_equal(np.asarray(t.result), g)


@pytest.mark.parametrize("axes", [(3, 0, 1, 2), (1, 2, 0, 3)],
                         ids=["batch-minor", "batch-middle"])
def test_staged_batch_keeps_the_requests_memory_order(server, axes):
    """Requests sliced from a pool laid out another way than C order
    (``np.asarray`` of a TPU array gives batch-minor) are staged in
    their own memory order, as a new concatenation would lay them out,
    and resolve bit-exact."""
    spy = SpyServer(server)
    q = serve_cnn.MicroBatchQueue(spy, max_batch=8, timeout_s=1e9)
    stored = np.ascontiguousarray(_req(server, 12).transpose(axes))
    pool = stored.transpose(np.argsort(axes))        # (12,) + item_shape
    reqs = [pool[5:8], pool[0:1], pool[2:6]]
    tickets = [q.submit(r) for r in reqs]            # 8 rows -> flush
    assert all(t.ok for t in tickets) and q.staging_allocs == 1
    (batch,) = spy.batches
    assert batch.base is q._staging
    assert batch.strides == np.concatenate(reqs).strides
    for t, want in zip(tickets, _fresh_logits(server, reqs)):
        np.testing.assert_array_equal(np.asarray(t.result), want)


def test_oversize_and_backpressure_flushes(server):
    """A group wider than max_batch (the last request overshoots it) is
    concatenated into a new array; a backpressure flush that fits is
    staged.  Both resolve bit-exact."""
    clock = FakeClock()
    spy = SpyServer(server)
    q = serve_cnn.MicroBatchQueue(spy, max_batch=8, timeout_s=1e9,
                                  clock=clock, max_pending=6,
                                  admission="flush")
    reqs = [_req(server, n) for n in (3, 2, 3)]
    tickets = [q.submit(r) for r in reqs]         # 5 + 3 > 6: backpressure
    assert [t.done for t in tickets] == [True, True, False]
    assert q.staging_allocs == 1 and spy.batches[-1].base is q._staging
    q.flush()
    assert all(t.ok for t in tickets)
    for t, want in zip(tickets[:2], _fresh_logits(server, reqs[:2])):
        np.testing.assert_array_equal(np.asarray(t.result), want)

    q = serve_cnn.MicroBatchQueue(spy, max_batch=8, timeout_s=1e9,
                                  clock=clock)
    reqs = [_req(server, n) for n in (5, 6)]      # 11 rows > max_batch
    tickets = [q.submit(r) for r in reqs]
    assert all(t.ok for t in tickets)
    assert q.staging_allocs == 0 and spy.batches[-1].shape[0] == 11
    for t, want in zip(tickets, _fresh_logits(server, reqs)):
        np.testing.assert_array_equal(np.asarray(t.result), want)


def test_single_request_group_passes_without_a_copy(server):
    spy = SpyServer(server)
    q = serve_cnn.MicroBatchQueue(spy, max_batch=8, timeout_s=1e9)
    req = _req(server, 3)
    t = q.submit(req)
    q.flush()
    assert t.ok and spy.batches == [req] and spy.batches[0] is req
    assert q.staging_allocs == 0


def test_fault_drops_the_staging_buffer(server):
    """A poisoned request in a staged flush: every healthy ticket
    resolves bit-exact, and the sub-flushes of the recovery write a new
    buffer, never the one the failed call was given."""
    from repro.runtime import resilience as rz

    clock = FakeClock()
    plan = rz.FaultPlan(poison_nan=True)
    chaos = rz.ChaosServer(server, plan, delay=lambda _dt: None)
    spy = SpyServer(chaos)
    q = serve_cnn.MicroBatchQueue(spy, max_batch=8, timeout_s=1e9,
                                  clock=clock, sleep=clock.advance,
                                  retry=rz.RetryPolicy(max_retries=0))
    warm = [_req(server, 4), _req(server, 4)]
    assert all(t.ok for t in [q.submit(r) for r in warm])
    assert q.staging_allocs == 1
    first = q._staging
    reqs = [_req(server, n) for n in (2, 1, 3, 2)]
    reqs[1][:] = np.nan
    tickets = [q.submit(r) for r in reqs]
    assert all(t.done for t in tickets)
    assert isinstance(tickets[1].error, rz.RequestPoisoned)
    healthy = [0, 2, 3]
    want = _fresh_logits(server, [reqs[i] for i in healthy])
    for i, w in zip(healthy, want):
        np.testing.assert_array_equal(np.asarray(tickets[i].result), w)
    # the root (8 rows) and the [2, 1] half failed: two fresh buffers
    assert q.staging_allocs == 3
    staged = [b for b in spy.batches if b.base is not None]
    assert staged[1].base is first                # the failed root call
    assert all(b.base is not first for b in staged[2:])


# ---------------------------------------------------------------------------
# The staged copy split into parts on a thread pool.
# ---------------------------------------------------------------------------


def _split_queue(server, monkeypatch, part_bytes):
    """A queue of 8 images whose staged copies split into parts of
    ``part_bytes``, on up to 8 threads; the spy server it serves, and
    the parts of each ``_assemble`` call."""
    monkeypatch.setattr(serve_cnn, "PART_BYTES", part_bytes)
    monkeypatch.setattr(serve_cnn, "POOL", 8)
    parts = []
    assemble = serve_cnn._assemble

    def counted(reqs, batch):
        parts.append(assemble(reqs, batch))
        return parts[-1]

    monkeypatch.setattr(serve_cnn, "_assemble", counted)
    spy = SpyServer(server)
    return serve_cnn.MicroBatchQueue(spy, max_batch=8, timeout_s=1e9), \
        spy, parts


# ``layout``: the pool's axes in memory order, outermost first
@pytest.mark.parametrize("layout,sizes,part_bytes,want_parts", [
    ((1, 2, 3, 0), (3, 1, 4), 10_000, 3),
    ((1, 2, 0, 3), (5, 3), 4_096, 8),
    ((0, 1, 2, 3), (2, 2, 4), 16_384, 2),
    (None, (1,) * 8, 6_000, 5),
], ids=["batch-minor", "batch-middle", "c-order", "single-images"])
def test_split_copy_matches_a_new_concatenation(server, monkeypatch, layout,
                                                sizes, part_bytes, want_parts):
    """A staged group of 32 KiB with parts of ``part_bytes`` is copied in
    ``want_parts`` parts along H (uneven ones included): the batch is
    bit-equal to a new concatenation, laid out as it, and the logits
    are bit-equal to the server's on it."""
    q, spy, parts = _split_queue(server, monkeypatch, part_bytes)
    if layout is None:
        reqs = [_req(server, 1) for _ in sizes]
    else:
        stored = np.ascontiguousarray(_req(server, 12).transpose(layout))
        pool = stored.transpose(np.argsort(layout))
        offs = np.cumsum((0,) + sizes)
        reqs = [pool[a + 1:b + 1] for a, b in zip(offs[:-1], offs[1:])]
    tickets = [q.submit(r) for r in reqs]            # 8 rows -> flush
    assert all(t.ok for t in tickets)
    assert parts == [want_parts] and q.split_assemblies == 1
    (batch,) = spy.batches
    want = np.concatenate(reqs)
    assert batch.base is q._staging and q.staging_allocs == 1
    assert batch.strides == want.strides
    np.testing.assert_array_equal(batch, want)
    for t, w in zip(tickets, _fresh_logits(server, reqs)):
        np.testing.assert_array_equal(np.asarray(t.result), w)


def test_failed_part_drops_the_staging_buffer(server, monkeypatch):
    """A part of a split copy raises: the group is bisected as after an
    infer fault, every ticket resolves bit-exact from a new buffer, and
    the half-written one is never given to the server."""
    q, spy, parts = _split_queue(server, monkeypatch, 8_192)

    class FailingPool:
        """A real pool whose second part of the first copy raises."""

        def __init__(self):
            self.pool = ThreadPoolExecutor(2)
            self.parts = 0

        def submit(self, fn, j):
            self.parts += 1
            if self.parts == 2:
                def fn(_j):
                    raise RuntimeError("part failed")
            return self.pool.submit(fn, j)

    failing = FailingPool()
    monkeypatch.setattr(serve_cnn, "_copy_pool", failing)
    reqs = [_req(server, n) for n in (2, 2, 1, 3)]
    tickets = [q.submit(r) for r in reqs]            # 8 rows -> flush
    assert all(t.ok for t in tickets)
    for t, w in zip(tickets, _fresh_logits(server, reqs)):
        np.testing.assert_array_equal(np.asarray(t.result), w)
    # the root's copy failed in 4 parts; the halves (16 KiB) split in 2
    assert parts == [2, 2] and q.split_assemblies == 2
    assert q.staging_allocs == 2 and len(spy.batches) == 2
    assert all(b.base is q._staging for b in spy.batches)
    failing.pool.shutdown()


def test_small_staged_group_copies_inline(server, monkeypatch):
    """A staged group under two parts' bytes is copied on the calling
    thread, in one part, and starts no thread pool."""
    monkeypatch.setattr(serve_cnn, "_copy_pool", None)
    q, spy, parts = _split_queue(server, monkeypatch, serve_cnn.PART_BYTES)
    reqs = [_req(server, n) for n in (3, 5)]
    tickets = [q.submit(r) for r in reqs]
    assert all(t.ok for t in tickets)
    assert parts == [1] and q.split_assemblies == 0
    assert serve_cnn._copy_pool is None
    np.testing.assert_array_equal(spy.batches[0], np.concatenate(reqs))


# ---------------------------------------------------------------------------
# Serving contract: no steady-state recompiles, arbitrary stream sizes.
# ---------------------------------------------------------------------------


def test_mixed_stream_zero_steady_state_recompiles(server):
    compiles = server.stats()["compiles"]
    q = serve_cnn.MicroBatchQueue(server, timeout_s=0.0)   # flush each submit
    sizes = [1, 3, 8, 2, 6, 13, 1, 7, 4, 29]               # incl. oversize
    tickets = serve_cnn.run_request_stream(q, sizes, seed=7)
    assert all(t.done for t in tickets)
    assert [t.size for t in tickets] == sizes
    assert server.stats()["compiles"] == compiles           # zero recompiles


def test_server_rejects_wrong_item_shape(server):
    with pytest.raises(ValueError, match="item shape"):
        server.infer(np.zeros((2, 8, 8, 1), np.float32))


def test_queue_rejects_bad_shape_without_poisoning_batch(server):
    """A malformed submit fails by itself; co-batched tickets still
    resolve (flush must never see an unconcatenatable queue)."""
    q = serve_cnn.MicroBatchQueue(server, max_batch=16, timeout_s=1e9)
    good = q.submit(_req(server, 2))
    with pytest.raises(ValueError, match="item shape"):
        q.submit(np.zeros((8, 8, 1), np.float32))
    with pytest.raises(ValueError, match="empty request"):
        q.submit(_req(server, 2)[:0])
    assert q.pending_images == 2
    q.flush()
    assert good.done and good.result.shape[0] == 2


def test_transient_infer_failure_recovers_in_flush(server, monkeypatch):
    """A transient infer failure (one OOM) must not orphan co-batched
    tickets: flush recovers internally (bisect + retry), FIFO order is
    preserved across the recovery, no ticket is executed twice after it
    resolves, and latency spans the ORIGINAL submit."""
    before = dict(server.stats())
    clock = FakeClock()
    q = serve_cnn.MicroBatchQueue(server, max_batch=16, timeout_s=1e9,
                                  clock=clock, sleep=clock.advance)
    reqs = [_req(server, 2), _req(server, 3), _req(server, 1)]
    tickets = [q.submit(r) for r in reqs]
    real_infer = server.infer
    calls, fails = [], {"left": 1}

    def flaky(x):
        calls.append(int(np.asarray(x).shape[0]))
        if fails["left"]:
            fails["left"] -= 1
            raise RuntimeError("transient oom")
        return real_infer(x)

    monkeypatch.setattr(server, "infer", flaky)
    clock.advance(0.010)                             # queue wait pre-fault
    q.flush()
    # full batch (6) failed once -> bisect: [t0] (2 rows) then [t1, t2]
    # (4 rows) each succeed exactly once -> no duplicated execution
    assert calls == [6, 2, 4]
    assert all(t.ok for t in tickets)
    # FIFO: each ticket's logits match its own request, in submit order
    for r, t in zip(reqs, tickets):
        ref = api.oracle(server.qnet, jnp.asarray(r), mode="packed")
        np.testing.assert_array_equal(np.asarray(t.result), np.asarray(ref))
    # latency spans the original submit (includes the pre-fault wait),
    # and the recovery never touched the retry budget (bisect halves
    # succeeded on their own)
    assert all(t.latency_s >= 0.010 for t in tickets)
    after = server.stats()
    assert after["retried"] == before["retried"]
    assert after["quarantined"] == before["quarantined"]


def test_single_ticket_transient_fault_retries_with_backoff(server,
                                                            monkeypatch):
    """An isolated failing ticket burns the retry budget with exponential
    backoff (driven through the injected sleep) and then succeeds —
    `retried` counts attempts, latency spans the original submit."""
    before = dict(server.stats())
    clock = FakeClock()
    retry = serve_cnn.resilience.RetryPolicy(max_retries=3, backoff_s=0.004,
                                             backoff_mult=2.0)
    q = serve_cnn.MicroBatchQueue(server, max_batch=16, timeout_s=1e9,
                                  clock=clock, sleep=clock.advance,
                                  retry=retry)
    t = q.submit(_req(server, 3))
    real_infer = server.infer
    fails = {"left": 2}

    def flaky(x):
        if fails["left"]:
            fails["left"] -= 1
            raise RuntimeError("transient oom")
        return real_infer(x)

    monkeypatch.setattr(server, "infer", flaky)
    q.flush()
    assert t.ok and t.result.shape[0] == 3
    after = server.stats()
    assert after["retried"] - before["retried"] == 2
    # backoff slept 0.004 then 0.008 on the fake clock; latency spans it
    assert t.latency_s == pytest.approx(0.012)


def test_build_qnet_registry_archs():
    for arch in ("lenet5", "fang_cnn", "vgg11"):
        qnet, item = serve_cnn.build_qnet(arch, smoke=True, num_steps=3,
                                          calib_batch=2)
        assert len(item) == 3
        assert qnet.num_steps == 3


@pytest.mark.parametrize("fail", [False, True], ids=["healthy", "failing"])
def test_main_reports_device_and_exit_status(fail, monkeypatch, capsys):
    """The CLI names the device it ran on and exits non-zero when any
    request did not resolve with logits (here: every infer raises, so
    every ticket is quarantined)."""
    from repro.launch import compile_cache

    monkeypatch.setattr(compile_cache, "enable", lambda: "off")
    if fail:
        def boom(self, x):
            raise RuntimeError("device lost")

        monkeypatch.setattr(serve_cnn.CNNServer, "infer", boom)
    rc = serve_cnn.main(["--arch", "lenet5", "--smoke", "--requests", "3",
                         "--max-request", "2", "--buckets", "1,2",
                         "--retries", "0"])
    out, err = capsys.readouterr()
    assert "platform=cpu" in out and "device_kind=" in out
    assert rc == (1 if fail else 0)
    assert ("FAILED: 3 of 3 requests" in err) == fail


def test_compile_cache_default_is_a_fixed_ignored_checkout_dir():
    """Without ``JAX_COMPILATION_CACHE_DIR`` the entry points cache at a
    fixed ``<checkout>/.jax_cache`` that git never commits (the helper
    itself is never called from tests)."""
    import pathlib

    from repro.launch import compile_cache

    root = pathlib.Path(__file__).resolve().parents[1]
    assert compile_cache.DEFAULT_DIR == root / ".jax_cache"
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    assert compile_cache.ENV_VAR == "JAX_COMPILATION_CACHE_DIR"


# ---------------------------------------------------------------------------
# serve_bench emits a well-formed BENCH_serve.json.
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_serve_bench_payload(tmp_path):
    from benchmarks import serve_bench

    out = tmp_path / "BENCH_serve.json"
    payload = serve_bench.run(log=lambda *_: None, archs=("lenet5",),
                              buckets=(1, 2), iters=2, n_requests=6,
                              max_request=3, json_path=out)
    on_disk = json.loads(out.read_text())
    assert on_disk == payload
    arch = payload["archs"]["lenet5"]
    assert {r["bucket"] for r in arch["buckets"]} == {1, 2}
    for row in arch["buckets"]:
        assert row["p50_ms"] > 0 and row["p95_ms"] >= row["p50_ms"]
        assert row["images_per_s"] > 0
    assert arch["stream"]["steady_state_recompiles"] == 0
    assert arch["stream"]["images"] > 0
    assert payload["config"]["devices"] >= 1
    # chaos section: fault rates in, recovery outcomes out, all reconciled
    chaos = payload["chaos"]
    assert chaos["arch"] == "lenet5"
    names = [row["scenario"] for row in chaos["scenarios"]]
    assert names == ["transient_fail_every_3", "poison_1_of_32",
                     "latency_spike_every_5"]
    for row in chaos["scenarios"]:
        assert row["bit_exact_healthy"]
        assert set(row["injected"]) == {"transient", "poison", "latency",
                                        "shard"}
        assert set(row["counters"]) == {"rejected", "shed", "retried",
                                        "quarantined", "degraded_flushes",
                                        "failures"}
    transient, poison, latency = chaos["scenarios"]
    assert transient["recovery_reconciles"]
    assert transient["resolved_ok"] == transient["requests"]
    assert poison["within_bound"]
    assert poison["counters"]["quarantined"] == 1
    assert poison["resolved_ok"] == poison["requests"] - 1
    assert latency["degraded"] and latency["counters"]["degraded_flushes"] > 0
