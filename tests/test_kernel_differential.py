"""Differential suite: every kernel strategy vs the pure-jnp oracles.

The autotuner (docs/kernels.md §7) made the execution strategy a free
variable: one ops-level call may run the sequential Pallas grid, the
plane-parallel grid, an int8/f32 MXU dot lowering, or the jitted XLA
twin.  This suite pins them all to ``kernels/ref.py`` bit-exactly across
the full surface — (m, k, n) / T / stride / padding / encoding
{radix, phase, ttfs} / dataflow {fused, bitserial} / sparsity on-off /
autotune on-off — so a tuning sweep can never trade correctness for
speed.

Layout: the ``Fast*`` classes are the fixed-seed CI subset (small,
exhaustive over the strategy axes at one shape each); the ``Fuzz*``
classes sweep shapes/data through the optional-hypothesis shim
(tests/_hyp.py — deterministic fixed-seed draws when hypothesis is not
installed) and are tagged ``slow`` for the full gate.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from _hyp import given, settings, st  # optional-hypothesis shim
from repro.core.encoding import (
    PhaseEncoding, RadixEncoding, TTFSEncoding,
)
from repro.kernels import ops, ref
from repro.kernels.autotune import (
    KernelConfig, conv_candidates, matmul_candidates,
)

RNG = np.random.default_rng(1234)

SPECS = {
    "radix": RadixEncoding(4),
    "phase": PhaseEncoding(6, periods=2),     # K = 3 packed bits
    "ttfs": TTFSEncoding(3),                  # pow2 out grid
}
DATAFLOWS = ("fused", "bitserial")


def _levels(rng, shape, spec):
    """Random packed activation levels on the spec's own grid."""
    bits = spec.kernel_schedule().packed_bits
    raw = rng.integers(0, 1 << bits, shape, dtype=np.uint8)
    if isinstance(spec, TTFSEncoding):
        from repro.core.encoding import pow2_floor
        raw = np.asarray(pow2_floor(jnp.asarray(raw, jnp.int32), bits),
                         np.uint8)
    return jnp.asarray(raw)


def _weights(rng, shape):
    return jnp.asarray(rng.integers(-8, 8, shape), jnp.int8)


def _matmul_want(x, w, spec, *, bias=None, mult=None):
    sched = spec.kernel_schedule()
    if mult is None:
        out = ref.radix_matmul_ref(x, w, sched.packed_bits,
                                   periods=sched.periods)
        return out if bias is None else out + bias.astype(jnp.int32)
    return ref.radix_matmul_epilogue_ref(
        x, w, bias, mult, sched.packed_bits, periods=sched.periods,
        grid=sched.out_grid)


def _conv_want(x, w, spec, *, stride=1, bias=None, mult=None):
    sched = spec.kernel_schedule()
    if mult is None:
        out = ref.radix_conv2d_ref(x, w, sched.packed_bits, stride=stride,
                                   periods=sched.periods)
        return out if bias is None else out + bias.astype(jnp.int32)
    return ref.radix_conv2d_epilogue_ref(
        x, w, bias, mult, sched.packed_bits, stride=stride,
        periods=sched.periods, grid=sched.out_grid)


def _assert_equal(got, want):
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Fast fixed-seed subset: every strategy axis at one awkward shape.
# ---------------------------------------------------------------------------


class TestFastMatmul:
    """(5, 19) @ (19, 11): nothing 8-aligned, every pad path live."""

    @pytest.mark.parametrize("enc", sorted(SPECS))
    @pytest.mark.parametrize("method", DATAFLOWS)
    @pytest.mark.parametrize("sparsity", [False, True])
    def test_raw(self, enc, method, sparsity):
        spec = SPECS[enc]
        x = _levels(RNG, (5, 19), spec)
        w = _weights(RNG, (19, 11))
        got = ops.radix_matmul(x, w, None, spec, method=method,
                               sparsity=sparsity)
        _assert_equal(got, _matmul_want(x, w, spec))

    @pytest.mark.parametrize("enc", sorted(SPECS))
    @pytest.mark.parametrize("method", DATAFLOWS)
    def test_epilogue(self, enc, method):
        spec = SPECS[enc]
        x = _levels(RNG, (5, 19), spec)
        w = _weights(RNG, (19, 11))
        bias = jnp.asarray(RNG.integers(-20, 20, (1, 11)), jnp.int32)
        mult = jnp.full((1, 11), 0.037, jnp.float32)
        got = ops.radix_matmul(x, w, bias, spec, method=method, mult=mult)
        _assert_equal(got, _matmul_want(x, w, spec, bias=bias, mult=mult))

    @pytest.mark.parametrize("method", DATAFLOWS)
    def test_every_candidate_config_matches_default(self, method):
        """The autotuner's whole search space is bit-exact: pinning any
        legal candidate via ``config=`` reproduces the default result."""
        spec = SPECS["radix"]
        x = _levels(RNG, (8, 24), spec)
        w = _weights(RNG, (24, 16))
        want = _matmul_want(x, w, spec)
        sched = spec.kernel_schedule()
        cands = matmul_candidates(8, 24, 16, sched, method, interpret=True)
        assert len(cands) >= 3            # default + xla twins at least
        for cand in cands:
            got = ops.radix_matmul(x, w, None, spec, method=method,
                                   config=cand)
            _assert_equal(got, want)

    def test_f32_act_layout_bit_identical(self):
        """act_dtype='f32': handing the kernel the same integer levels in
        the f32 GEMM layout (the engine-free caller's option) is
        bit-identical to the packed uint8 path."""
        spec = SPECS["radix"]
        x = _levels(RNG, (8, 24), spec)
        w = _weights(RNG, (24, 16))
        cfg = KernelConfig(impl="xla", mxu_dtype="f32", act_dtype="f32")
        want = _matmul_want(x, w, spec)
        got_u8 = ops.radix_matmul(x, w, None, spec, method="fused",
                                  config=cfg)
        got_f32 = ops.radix_matmul(x.astype(jnp.float32), w, None, spec,
                                   method="fused", config=cfg)
        _assert_equal(got_u8, want)
        _assert_equal(got_f32, want)

    def test_f32_act_rejected_off_the_fused_xla_twin(self):
        spec = SPECS["radix"]
        x = _levels(RNG, (4, 16), spec)
        w = _weights(RNG, (16, 8))
        bad = KernelConfig(impl="xla", mxu_dtype="f32", act_dtype="f32")
        with pytest.raises(ValueError, match="act_dtype"):
            ops.radix_matmul(x, w, None, spec, method="bitserial",
                             config=bad)

    def test_autotune_on_off_bit_equal(self, monkeypatch):
        from repro.kernels import autotune as at

        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", "")
        at.reset_default_cache()
        try:
            spec = SPECS["ttfs"]
            x = _levels(RNG, (4, 16), spec)
            w = _weights(RNG, (16, 8))
            bias = jnp.asarray(RNG.integers(-10, 10, (1, 8)), jnp.int32)
            mult = jnp.full((1, 8), 0.05, jnp.float32)
            base = ops.radix_matmul(x, w, bias, spec, method="bitserial",
                                    mult=mult, sparsity=True)
            tuned = ops.radix_matmul(x, w, bias, spec, method="bitserial",
                                     mult=mult, sparsity=True,
                                     autotune=True)
            _assert_equal(tuned, base)
            _assert_equal(base, _matmul_want(x, w, spec, bias=bias,
                                             mult=mult))
        finally:
            at.reset_default_cache()


class TestFastConv:
    """4x5 image, 3 channels -> 7: odd everywhere."""

    @pytest.mark.parametrize("enc", sorted(SPECS))
    @pytest.mark.parametrize("method", DATAFLOWS)
    @pytest.mark.parametrize("stride", [1, 2])
    def test_raw(self, enc, method, stride):
        spec = SPECS[enc]
        x = _levels(RNG, (2, 5, 6, 3), spec)
        w = _weights(RNG, (3, 3, 3, 7))
        got = ops.radix_conv2d(x, w, None, spec, method=method,
                               stride=stride)
        _assert_equal(got, _conv_want(x, w, spec, stride=stride))

    @pytest.mark.parametrize("enc", sorted(SPECS))
    @pytest.mark.parametrize("method", DATAFLOWS)
    def test_epilogue_same_padding_sparsity(self, enc, method):
        spec = SPECS[enc]
        x = _levels(RNG, (2, 5, 5, 3), spec)
        # zero a channel so the sparsity prepass actually skips planes
        x = x.at[..., 0].set(0)
        w = _weights(RNG, (3, 3, 3, 7))
        bias = jnp.asarray(RNG.integers(-20, 20, (7,)), jnp.int32)
        mult = jnp.full((7,), 0.041, jnp.float32)
        got = ops.radix_conv2d(x, w, bias, spec, method=method,
                               padding="SAME", mult=mult, sparsity=True)
        xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
        _assert_equal(got, _conv_want(xp, w, spec, bias=bias.reshape(1, -1),
                                      mult=mult.reshape(1, -1)))

    @pytest.mark.parametrize("method", DATAFLOWS)
    def test_every_candidate_config_matches_default(self, method):
        spec = SPECS["phase"]
        x = _levels(RNG, (2, 6, 6, 4), spec)
        w = _weights(RNG, (3, 3, 4, 8))
        want = _conv_want(x, w, spec, stride=2)
        sched = spec.kernel_schedule()
        cands = conv_candidates(6, 6, 4, 3, 3, 8, sched, method,
                                interpret=True)
        assert len(cands) >= 3
        for cand in cands:
            got = ops.radix_conv2d(x, w, None, spec, method=method,
                                   stride=2, config=cand)
            _assert_equal(got, want)


# ---------------------------------------------------------------------------
# Property sweeps: shapes/data drawn through the _hyp shim.  Shapes are
# sampled from small pools so jit caching keeps the sweep tractable.
# ---------------------------------------------------------------------------


MATMUL_SHAPES = [(1, 8, 8), (3, 17, 5), (8, 32, 16), (9, 24, 13)]
CONV_SHAPES = [(1, 5, 5, 1, 3, 4), (2, 6, 7, 3, 3, 5), (1, 8, 8, 2, 5, 6)]


@pytest.mark.slow
class TestFuzzMatmul:
    @given(
        st.sampled_from(MATMUL_SHAPES),
        st.integers(1, 6),                      # T
        st.sampled_from(DATAFLOWS),
        st.booleans(),                          # sparsity
        st.booleans(),                          # epilogue
        st.integers(0, 2 ** 31 - 1),            # data seed
    )
    @settings(max_examples=20, deadline=None)
    def test_matches_ref(self, shape, T, method, sparsity, epilogue, seed):
        m, k, n = shape
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.integers(0, 1 << T, (m, k)), jnp.uint8)
        w = _weights(rng, (k, n))
        if epilogue:
            bias = jnp.asarray(rng.integers(-30, 30, (1, n)), jnp.int32)
            mult = jnp.asarray(
                rng.uniform(0.01, 0.2, (1, n)).astype(np.float32))
            got = ops.radix_matmul(x, w, bias, T, method=method, mult=mult,
                                   sparsity=sparsity)
            want = ref.radix_matmul_epilogue_ref(x, w, bias, mult, T)
        else:
            got = ops.radix_matmul(x, w, None, T, method=method,
                                   sparsity=sparsity)
            want = ref.radix_matmul_ref(x, w, T)
        _assert_equal(got, want)

    @given(
        st.sampled_from(MATMUL_SHAPES),
        st.sampled_from(sorted(SPECS)),
        st.sampled_from(DATAFLOWS),
        st.integers(0, 2 ** 31 - 1),
    )
    @settings(max_examples=12, deadline=None)
    def test_encodings_match_ref(self, shape, enc, method, seed):
        m, k, n = shape
        spec = SPECS[enc]
        rng = np.random.default_rng(seed)
        x = _levels(rng, (m, k), spec)
        w = _weights(rng, (k, n))
        got = ops.radix_matmul(x, w, None, spec, method=method,
                               sparsity=True)
        _assert_equal(got, _matmul_want(x, w, spec))


@pytest.mark.slow
class TestFuzzConv:
    @given(
        st.sampled_from(CONV_SHAPES),
        st.integers(1, 5),                      # T
        st.sampled_from(DATAFLOWS),
        st.integers(1, 2),                      # stride
        st.sampled_from(["VALID", "SAME"]),
        st.integers(0, 2 ** 31 - 1),
    )
    @settings(max_examples=12, deadline=None)
    def test_matches_ref(self, shape, T, method, stride, padding, seed):
        b, h, w_, cin, kk, cout = shape
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.integers(0, 1 << T, (b, h, w_, cin)), jnp.uint8)
        w = _weights(rng, (kk, kk, cin, cout))
        got = ops.radix_conv2d(x, w, None, T, method=method, stride=stride,
                               padding=padding)
        xp = x
        if padding == "SAME":
            ph = ops.same_pads(h, kk, stride)
            pw = ops.same_pads(w_, kk, stride)
            xp = jnp.pad(x, ((0, 0), ph, pw, (0, 0)))
        _assert_equal(got, ref.radix_conv2d_ref(xp, w, T, stride=stride))


@pytest.mark.slow
class TestFuzzConfigDifferential:
    """Random pinned configs vs the default strategy on random data —
    the autotuner can pick ANY of these, so all must agree."""

    @given(
        st.sampled_from(MATMUL_SHAPES),
        st.sampled_from(DATAFLOWS),
        st.sampled_from(["int8", "f32"]),
        st.sampled_from(["pallas", "xla"]),
        st.booleans(),                          # plane_parallel
        st.integers(0, 2 ** 31 - 1),
    )
    @settings(max_examples=15, deadline=None)
    def test_matmul_config(self, shape, method, mxu_dtype, impl, pp, seed):
        from repro.kernels.autotune import exact_lowering

        m, k, n = shape
        T = 3
        if not exact_lowering(mxu_dtype, max_operand=(1 << T) - 1,
                              k_contract=k, method=method):
            return                     # the sweep would never offer it
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.integers(0, 1 << T, (m, k)), jnp.uint8)
        w = _weights(rng, (k, n))
        cfg = KernelConfig(impl=impl, mxu_dtype=mxu_dtype,
                           plane_parallel=pp and impl == "pallas")
        got = ops.radix_matmul(x, w, None, T, method=method, config=cfg)
        _assert_equal(got, ref.radix_matmul_ref(x, w, T))
