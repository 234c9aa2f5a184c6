"""The block-size/strategy autotuner (repro.kernels.autotune).

Covers the machinery the kernels build on: the winner cache (hit/miss/
disk counters, key anatomy — a key that dropped the dataflow or encoding
schedule would alias distinct problems), deterministic winner selection
under an injectable timer, the exactness gate for non-default MXU dot
lowerings, and the end-to-end ops.radix_matmul(autotune=True) path.
"""

import json

import jax
import numpy as np
import pytest

from repro.core import encoding
from repro.kernels import autotune as at
from repro.kernels.autotune import (
    AutotuneCache, KernelConfig, conv_key, exact_lowering,
    matmul_candidates, matmul_key, tune,
)


def _sched(T=4, periods=1, out_grid="dense"):
    return encoding.KernelSchedule(packed_bits=T, periods=periods,
                                   out_grid=out_grid)


# ---------------------------------------------------------------------------
# KernelConfig + exactness gate.
# ---------------------------------------------------------------------------


class TestKernelConfig:
    def test_roundtrip(self):
        cfg = KernelConfig(impl="xla", mxu_dtype="f32", bm=64,
                           plane_parallel=True)
        assert KernelConfig.from_dict(cfg.as_dict()) == cfg

    def test_validates(self):
        with pytest.raises(ValueError):
            KernelConfig(impl="cuda")
        with pytest.raises(ValueError):
            KernelConfig(mxu_dtype="int4")
        with pytest.raises(ValueError):
            KernelConfig(mxu_dtype="int32")

    def test_default_is_untuned_heuristic(self):
        """The first candidate everywhere: the 128-tile Pallas path on the
        int8 lowering (the TPU compiler refuses int32 x int32 dots)."""
        cfg = KernelConfig()
        assert (cfg.impl, cfg.mxu_dtype) == ("pallas", "int8")
        assert (cfg.bm, cfg.bk, cfg.bn, cfg.bco) == (128, 128, 128, 128)
        assert not cfg.plane_parallel


class TestExactLowering:
    def test_int32_always_exact(self):
        """int32 is no lowering any more (the TPU refuses int32 x int32
        dots); int8 took its place as the always-exact default."""
        assert "int32" not in at.MXU_DTYPES
        with pytest.raises(ValueError):
            exact_lowering("int32", max_operand=255, k_contract=1 << 20,
                           method="fused")
        assert exact_lowering("int8", max_operand=255, k_contract=1 << 20,
                              method="fused")

    def test_int8_operand_bound(self):
        """int8 holds values <= 127: bit planes and T <= 7 packed levels
        go in whole; wider fused operands are sliced into 7-bit pieces,
        so the lowering stays exact — checked here against int32 math."""
        from repro.kernels.radix_matmul import int8_contract, mxu_dot
        assert exact_lowering("int8", max_operand=1, k_contract=4096,
                              method="bitserial")
        assert exact_lowering("int8", max_operand=127, k_contract=4096,
                              method="fused")
        rng = np.random.default_rng(3)
        w = rng.integers(-128, 128, (64, 16)).astype(np.int8)
        want_w = w.astype(np.int64)
        for bits in (7, 8, 12):
            a = rng.integers(0, 1 << bits, (8, 64)).astype(np.int32)
            got = np.asarray(mxu_dot(a, w, "int8", a_bits=bits))
            np.testing.assert_array_equal(got, a.astype(np.int64) @ want_w)
        # both operands wide (attention's qq . kq): slices on each side
        a = rng.integers(0, 256, (4, 32)).astype(np.int32)
        b = rng.integers(0, 1024, (32, 8)).astype(np.int32)
        got = np.asarray(int8_contract(
            lambda x, y: jax.lax.dot_general(
                x, y, (((1,), (0,)), ((), ())),
                preferred_element_type=jax.numpy.int32),
            a, b, a_bits=8, b_bits=10))
        np.testing.assert_array_equal(got, a.astype(np.int64) @ b)

    def test_f32_partial_sum_bound(self):
        """f32 accumulates exactly below 2^24; the guard keeps the worst
        per-k-tile partial sum under half of that."""
        assert exact_lowering("f32", max_operand=15, k_contract=128,
                              method="fused")
        assert not exact_lowering("f32", max_operand=255,
                                  k_contract=1 << 16, method="fused")


# ---------------------------------------------------------------------------
# Key anatomy: every schedule/dataflow axis must separate keys.
# ---------------------------------------------------------------------------


class TestKeys:
    def test_dataflow_separates(self):
        a = matmul_key(8, 16, 8, _sched(), "fused", epilogue=False,
                       sparsity=False, backend="cpu")
        b = matmul_key(8, 16, 8, _sched(), "bitserial", epilogue=False,
                       sparsity=False, backend="cpu")
        assert a != b

    def test_schedule_separates(self):
        """radix T=4 vs phase T=4/P=2 pack identical bytes but replay
        different plane schedules — one winner must not serve both."""
        kw = dict(epilogue=False, sparsity=False, backend="cpu")
        radix = matmul_key(8, 16, 8, _sched(T=4), "bitserial", **kw)
        phase = matmul_key(8, 16, 8, _sched(T=4, periods=2), "bitserial",
                           **kw)
        assert radix != phase

    def test_out_grid_separates_only_with_epilogue(self):
        kw = dict(sparsity=False, backend="cpu")
        dense = matmul_key(8, 16, 8, _sched(out_grid="dense"), "fused",
                           epilogue=True, **kw)
        pow2 = matmul_key(8, 16, 8, _sched(out_grid="pow2"), "fused",
                          epilogue=True, **kw)
        assert dense != pow2
        # raw accumulators never run the projection -> grid folds away
        raw_a = matmul_key(8, 16, 8, _sched(out_grid="dense"), "fused",
                           epilogue=False, **kw)
        raw_b = matmul_key(8, 16, 8, _sched(out_grid="pow2"), "fused",
                           epilogue=False, **kw)
        assert raw_a == raw_b

    def test_epilogue_sparsity_shape_separate(self):
        base = dict(epilogue=False, sparsity=False, backend="cpu")
        k0 = matmul_key(8, 16, 8, _sched(), "fused", **base)
        assert k0 != matmul_key(8, 16, 8, _sched(), "fused",
                                epilogue=True, sparsity=False, backend="cpu")
        assert k0 != matmul_key(8, 16, 8, _sched(), "fused",
                                epilogue=False, sparsity=True, backend="cpu")
        assert k0 != matmul_key(16, 16, 8, _sched(), "fused", **base)

    def test_conv_key_includes_geometry(self):
        kw = dict(batch=2, epilogue=False, sparsity=False, backend="cpu")
        a = conv_key(8, 8, 3, 3, 3, 16, 1, _sched(), "fused", **kw)
        b = conv_key(8, 8, 3, 3, 3, 16, 2, _sched(), "fused", **kw)
        assert a != b                     # stride
        c = conv_key(8, 8, 3, 5, 5, 16, 1, _sched(), "fused", **kw)
        assert a != c                     # kernel size

    def test_forced_collision_is_the_same_problem(self):
        """Identical problems DO collide — that's the cache working."""
        a = matmul_key(8, 16, 8, _sched(), "fused", epilogue=True,
                       sparsity=True, backend="cpu")
        b = matmul_key(8, 16, 8, _sched(T=4), "fused", epilogue=True,
                       sparsity=True, backend="cpu")
        assert a == b


# ---------------------------------------------------------------------------
# Candidates.
# ---------------------------------------------------------------------------


class TestCandidates:
    def test_first_candidate_is_the_default(self):
        """An interrupted sweep can never regress below the untuned path:
        position 0 is always KernelConfig() (ties break by order)."""
        for method in ("fused", "bitserial"):
            cands = matmul_candidates(128, 256, 128, _sched(), method,
                                      interpret=False)
            assert cands[0] == KernelConfig()

    def test_bitserial_sweeps_plane_parallel_fused_does_not(self):
        fused = matmul_candidates(128, 256, 128, _sched(), "fused",
                                  interpret=False)
        bits = matmul_candidates(128, 256, 128, _sched(), "bitserial",
                                 interpret=False)
        assert not any(c.plane_parallel for c in fused)
        assert any(c.plane_parallel for c in bits)

    def test_only_exact_lowerings_offered(self):
        """f32 is offered only under its mantissa bound; int8 (sliced
        when the operand is wider than int8) always is."""
        cands = matmul_candidates(64, 1 << 16, 64, _sched(T=8), "fused",
                                  interpret=False)
        assert not any(c.mxu_dtype == "f32" for c in cands)
        assert any(c.mxu_dtype == "int8" for c in cands)
        cands4 = matmul_candidates(64, 64, 64, _sched(T=4), "fused",
                                   interpret=False)
        assert {c.mxu_dtype for c in cands4} == {"int8", "f32"}

    def test_no_duplicates(self):
        cands = matmul_candidates(8, 16, 8, _sched(), "bitserial",
                                  interpret=True)
        assert len(cands) == len(set(cands))

    def test_f32_act_only_on_fused_xla_twin(self):
        """act_dtype='f32' is an XLA-fused-only layout: bit-serial plane
        extraction needs the packed bytes, and the Pallas programs take
        the packed layout by contract."""
        fused = matmul_candidates(128, 256, 128, _sched(), "fused",
                                  interpret=False)
        f32_act = [c for c in fused if c.act_dtype == "f32"]
        assert f32_act and all(c.impl == "xla" for c in f32_act)
        bits = matmul_candidates(128, 256, 128, _sched(), "bitserial",
                                 interpret=False)
        assert not any(c.act_dtype == "f32" for c in bits)

    def test_plan_sweep_excludes_f32_act(self):
        """Compiled plans pass act_dtypes=("u8",): their inter-layer
        contract ships packed uint8 activations."""
        cands = matmul_candidates(128, 256, 128, _sched(), "fused",
                                  interpret=False, act_dtypes=("u8",))
        assert not any(c.act_dtype == "f32" for c in cands)

    def test_f32_act_requires_exact_f32_lowering(self):
        """No f32-layout candidate when the partial sum can escape the
        24-bit mantissa (the same gate as mxu_dtype='f32')."""
        cands = matmul_candidates(64, 1 << 16, 64, _sched(T=8), "fused",
                                  interpret=False)
        assert not any(c.act_dtype == "f32" for c in cands)

    def test_act_dtype_validates(self):
        with pytest.raises(ValueError):
            KernelConfig(act_dtype="bf16")


# ---------------------------------------------------------------------------
# Cache counters + disk round-trip.
# ---------------------------------------------------------------------------


class TestCache:
    def test_hit_miss_counters(self):
        cache = AutotuneCache(None)
        key = ("matmul", "cpu", 1)
        assert cache.get(key) is None
        assert cache.stats.misses == 1
        cache.put(key, KernelConfig(impl="xla"), 12.5)
        assert cache.get(key) == KernelConfig(impl="xla")
        assert cache.stats.hits == 1
        assert cache.stats.disk_hits == 0

    def test_disk_roundtrip(self, tmp_path):
        path = tmp_path / "autotune.json"
        a = AutotuneCache(path)
        key = matmul_key(8, 16, 8, _sched(), "fused", epilogue=False,
                         sparsity=False, backend="cpu")
        a.put(key, KernelConfig(impl="xla", mxu_dtype="f32"), 3.0)
        # a second process: fresh cache object, same file
        b = AutotuneCache(path)
        assert b.get(key) == KernelConfig(impl="xla", mxu_dtype="f32")
        assert b.stats.disk_hits == 1 and b.stats.hits == 1
        # the payload is versioned JSON, inspectable by humans
        payload = json.loads(path.read_text())
        assert payload["version"] == 1 and len(payload["entries"]) == 1

    def test_corrupt_disk_table_is_cold_cache(self, tmp_path):
        path = tmp_path / "autotune.json"
        path.write_text("{not json")
        cache = AutotuneCache(path)
        key = ("matmul", "cpu", 2)
        assert cache.get(key) is None          # no raise
        cache.put(key, KernelConfig(), 1.0)    # and the file heals
        assert json.loads(path.read_text())["version"] == 1

    def test_env_var_disables_disk(self, tmp_path, monkeypatch):
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", "")
        assert at.cache_path() is None
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                           str(tmp_path / "t.json"))
        assert at.cache_path() == tmp_path / "t.json"


# ---------------------------------------------------------------------------
# The tuning loop: injectable timer, deterministic winner.
# ---------------------------------------------------------------------------


class TestTune:
    def _candidates(self):
        return [KernelConfig(),
                KernelConfig(impl="xla", mxu_dtype="int8"),
                KernelConfig(impl="xla", mxu_dtype="f32")]

    def test_deterministic_winner_under_fake_timer(self):
        cache = AutotuneCache(None)
        times = {"pallas/int8": 30.0, "xla/int8": 10.0, "xla/f32": 20.0}

        def build(cfg):
            return lambda: f"{cfg.impl}/{cfg.mxu_dtype}"

        win = tune(("k", 1), self._candidates(), build, cache=cache,
                   timer=lambda thunk: times[thunk()])
        assert win == KernelConfig(impl="xla", mxu_dtype="int8")
        assert cache.stats.sweeps == 1

    def test_tie_breaks_by_candidate_order(self):
        """Equal times -> earliest candidate (the untuned default) wins:
        selection is reproducible under any timer."""
        cache = AutotuneCache(None)
        win = tune(("k", 2), self._candidates(),
                   lambda cfg: (lambda: None), cache=cache,
                   timer=lambda thunk: 7.0)
        assert win == KernelConfig()

    def test_failing_candidates_skipped(self):
        cache = AutotuneCache(None)

        def build(cfg):
            if cfg.impl == "pallas":
                raise RuntimeError("illegal tile")
            return lambda: None

        win = tune(("k", 3), self._candidates(), build, cache=cache,
                   timer=lambda thunk: 1.0)
        assert win.impl == "xla"
        # skipped, but not in silence: counted, first error kept
        assert cache.stats.skipped == 1
        assert "illegal tile" in cache.stats.first_error

    def test_all_failing_raises(self):
        cache = AutotuneCache(None)
        with pytest.raises(RuntimeError):
            tune(("k", 4), self._candidates(),
                 lambda cfg: (_ for _ in ()).throw(RuntimeError()),
                 cache=cache, timer=lambda thunk: 1.0)

    def test_second_call_hits_never_resweeps(self):
        cache = AutotuneCache(None)
        calls = []

        def timer(thunk):
            calls.append(1)
            return 1.0

        for _ in range(3):
            tune(("k", 5), self._candidates(),
                 lambda cfg: (lambda: None), cache=cache, timer=timer)
        assert cache.stats.sweeps == 1
        assert len(calls) == len(self._candidates())
        assert cache.stats.hits == 2

    def test_distinct_keys_sweep_separately(self):
        """The forced-collision converse: fused and bitserial winners are
        tuned (and stored) independently even for identical shapes."""
        cache = AutotuneCache(None)
        kw = dict(epilogue=False, sparsity=False, backend="cpu")
        kf = matmul_key(8, 16, 8, _sched(), "fused", **kw)
        kb = matmul_key(8, 16, 8, _sched(), "bitserial", **kw)
        tune(kf, self._candidates(), lambda cfg: (lambda: None),
             cache=cache, timer=lambda t: 1.0)
        tune(kb, [KernelConfig(impl="xla", mxu_dtype="f32")],
             lambda cfg: (lambda: None), cache=cache,
             timer=lambda t: 1.0)
        assert cache.stats.sweeps == 2
        assert cache.get(kf) == KernelConfig()
        assert cache.get(kb) == KernelConfig(impl="xla", mxu_dtype="f32")


# ---------------------------------------------------------------------------
# End to end: ops-level autotune stays bit-exact and caches.
# ---------------------------------------------------------------------------


class TestOpsAutotune:
    def test_radix_matmul_autotune_bit_exact(self, monkeypatch):
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", "")
        at.reset_default_cache()
        try:
            from repro.kernels import ops
            from repro.kernels.ref import radix_matmul_ref

            rng = np.random.default_rng(0)
            x = rng.integers(0, 16, (8, 24), dtype=np.uint8)
            w = rng.integers(-8, 8, (24, 8), dtype=np.int32)
            want = np.asarray(radix_matmul_ref(x, w, 4))
            base = np.asarray(ops.radix_matmul(x, w, None, 4))
            tuned = np.asarray(ops.radix_matmul(x, w, None, 4,
                                                autotune=True))
            np.testing.assert_array_equal(base, want)
            np.testing.assert_array_equal(tuned, want)
            stats = at.default_cache().stats
            assert stats.sweeps == 1
            # steady state: same problem again is a pure cache hit
            np.testing.assert_array_equal(
                np.asarray(ops.radix_matmul(x, w, None, 4, autotune=True)),
                want)
            assert at.default_cache().stats.sweeps == 1
            assert at.default_cache().stats.hits >= 1
        finally:
            at.reset_default_cache()
