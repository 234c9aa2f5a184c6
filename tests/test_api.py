"""repro.api facade: EncodingSpec polymorphism + Accelerator/Executable.

The paper's claim — one accelerator, swappable neural encodings — as an
API contract:

* ``RadixEncoding`` runs on both backends and stays bit-exact against the
  oracle paths (the kernels sweep across T lives in
  tests/test_fused_epilogue.py).
* ``RateEncoding`` executes end-to-end through ``Accelerator.compile`` on
  the jnp backend, plan-vs-oracle exact — the first time rate coding is a
  runnable path rather than a dead helper.
* Invalid (backend, dataflow, encoding, net) pairings fail loudly at
  compile time; nothing silently falls through.
* ``Executable.stats()`` exposes the plan-cache counters across padding /
  top-bucket chunking / mixed streams (the PlanCache edge cases).
"""

import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from _nets import trained_lenet
from repro import api
from repro.core import conversion
from repro.models import fang, lenet

RNG = np.random.default_rng(13)


def _make(maker=lenet, pool_mode="or", width_mult=0.25, **convert_kw):
    static, params, input_hw = maker.make(pool_mode=pool_mode,
                                          width_mult=width_mult)
    calib = jnp.asarray(RNG.uniform(0, 1, (4,) + input_hw), jnp.float32)
    qnet = conversion.convert(static, params, calib, **convert_kw)
    return qnet, input_hw


def _x(batch, input_hw):
    return jnp.asarray(RNG.uniform(0, 1, (batch,) + input_hw), jnp.float32)


# ---------------------------------------------------------------------------
# EncodingSpec declarations.
# ---------------------------------------------------------------------------


class TestEncodingSpecs:
    def test_radix_declarations(self):
        spec = api.RadixEncoding(4)
        assert spec.levels == 16 and spec.max_level == 15
        assert "kernels" in spec.backends and "jnp" in spec.backends
        assert spec.kernel_dataflows == ("fused", "bitserial")
        assert spec.validate_dataflow(None) == "fused"
        assert spec.supports_pool("or") and spec.supports_pool("max")

    def test_rate_declarations(self):
        spec = api.RateEncoding(7)
        assert spec.levels == 8 and spec.max_level == 7
        assert spec.backends == ("jnp",)
        assert spec.kernel_dataflows == ()
        with pytest.raises(ValueError, match="kernel dataflow"):
            spec.validate_dataflow("fused")
        assert spec.supports_pool("avg") and not spec.supports_pool("or")

    def test_specs_hashable_and_comparable(self):
        assert api.RadixEncoding(4) == api.RadixEncoding(4)
        assert api.RadixEncoding(4) != api.RadixEncoding(5)
        assert api.RadixEncoding(1) != api.RateEncoding(1)
        assert len({api.RadixEncoding(4), api.RadixEncoding(4),
                    api.RateEncoding(4)}) == 2

    def test_invalid_spec_params(self):
        with pytest.raises(ValueError, match="num_steps"):
            api.RadixEncoding(0)
        with pytest.raises(ValueError, match="scale"):
            api.RateEncoding(4, scale=0.0)

    def test_kernel_capable_specs_require_consistent_schedule(self):
        """Kernels capability is a per-spec KernelSchedule declaration;
        a subclass declaring dataflows with a schedule its own level
        algebra cannot ride (extraction bits too narrow for max_level,
        or an unknown epilogue grid) must be rejected instead of
        silently diverging from its requantize."""
        import dataclasses
        from typing import ClassVar, Tuple

        @dataclasses.dataclass(frozen=True)
        class NarrowSpec(api.RadixEncoding):
            """Declares one bit fewer than its levels need."""

            name: ClassVar[str] = "narrow"
            kernel_dataflows: ClassVar[Tuple[str, ...]] = ("fused",)

            def kernel_schedule(self):
                return dataclasses.replace(
                    super().kernel_schedule(),
                    packed_bits=self.num_steps - 1)

        with pytest.raises(ValueError, match="schedule is inconsistent"):
            NarrowSpec(4).validate_dataflow(None)
        from repro.kernels import ops
        with pytest.raises(ValueError, match="schedule is inconsistent"):
            ops._steps(NarrowSpec(4))

        @dataclasses.dataclass(frozen=True)
        class BadGridSpec(api.RadixEncoding):
            name: ClassVar[str] = "badgrid"

            def kernel_schedule(self):
                return dataclasses.replace(
                    super().kernel_schedule(), out_grid="fibonacci")

        with pytest.raises(ValueError, match="out_grid"):
            BadGridSpec(4).validate_dataflow(None)

    def test_kernel_schedule_declarations(self):
        """The shipped schedules: dense for radix/phase, pow2 for TTFS;
        jnp-only specs have none."""
        assert api.RadixEncoding(4).kernel_schedule() == api.KernelSchedule(
            packed_bits=4, periods=1, out_level=15, out_grid="dense")
        assert api.PhaseEncoding(8, periods=2).kernel_schedule() == \
            api.KernelSchedule(packed_bits=4, periods=2, out_level=15,
                               out_grid="dense")
        assert api.TTFSEncoding(4).kernel_schedule() == api.KernelSchedule(
            packed_bits=4, periods=1, out_level=15, out_grid="pow2")
        with pytest.raises(ValueError, match="kernel dataflow"):
            api.RateEncoding(4).kernel_schedule()

    def test_rate_integer_sigma_delta_exact(self):
        spec = api.RateEncoding(9)
        q = jnp.arange(10, dtype=jnp.int32)
        planes = spec.encode(q)
        assert planes.shape == (9, 10)
        np.testing.assert_array_equal(np.asarray(spec.decode(planes)),
                                      np.asarray(q))

    def test_convert_stores_spec(self):
        qnet, _ = _make(num_steps=4)
        assert qnet.encoding == api.RadixEncoding(4)
        assert qnet.spec == api.RadixEncoding(4)
        qnet, _ = _make(pool_mode="avg", encoding=api.RateEncoding(6))
        assert qnet.spec == api.RateEncoding(6)
        assert qnet.num_steps == 6

    def test_convert_validates_spec_args(self):
        static, params, input_hw = lenet.make(pool_mode="or",
                                              width_mult=0.25)
        calib = jnp.asarray(RNG.uniform(0, 1, (2,) + input_hw), jnp.float32)
        with pytest.raises(ValueError, match="num_steps"):
            conversion.convert(static, params, calib)
        with pytest.raises(ValueError, match="contradicts"):
            conversion.convert(static, params, calib, num_steps=3,
                               encoding=api.RadixEncoding(4))
        # rate + or-pool: the per-plane path does not commute -> loud error
        with pytest.raises(ValueError, match="pool mode"):
            conversion.convert(static, params, calib,
                               encoding=api.RateEncoding(6))


# ---------------------------------------------------------------------------
# RateEncoding end-to-end (the jnp backend).
# ---------------------------------------------------------------------------


class TestRateEndToEnd:
    @pytest.mark.parametrize("T", [3, 7])
    def test_rate_plan_vs_oracle(self, T):
        """Compiled (jitted, bucketed) rate executable == the spike-plane
        oracle == the packed twin, bit-exact, including pad + chunk."""
        qnet, hw = _make(pool_mode="avg", encoding=api.RateEncoding(T))
        exe = api.Accelerator(backend="jnp").compile(qnet, hw,
                                                     buckets=(1, 4))
        for n in (1, 3, 4, 9):
            x = _x(n, hw)
            want = api.oracle(qnet, x, mode="snn")
            np.testing.assert_array_equal(
                np.asarray(api.oracle(qnet, x, mode="packed")),
                np.asarray(want))
            np.testing.assert_array_equal(np.asarray(exe(x)),
                                          np.asarray(want))

    def test_rate_fang_cnn(self):
        qnet, hw = _make(fang, pool_mode="avg",
                         encoding=api.RateEncoding(5))
        exe = api.Accelerator(backend="jnp").compile(qnet, hw, buckets=(2,))
        x = _x(2, hw)
        np.testing.assert_array_equal(
            np.asarray(exe(x)), np.asarray(api.oracle(qnet, x, mode="snn")))

    def test_rate_scale_headroom_folds_into_conversion(self):
        """RateEncoding(scale=k): the headroom factor must reach the
        bias/multiplier/logit folding, not just quantize — regression for
        scale only being applied on the activation side (which mis-scaled
        biases 2x and zeroed every logit)."""
        # a trained net: argmax agreement is meaningful only where the
        # float decisions have margins (tests/_nets.py)
        static, params, _, calib = trained_lenet("avg", calib_batch=16)
        ref = np.asarray(
            conversion.float_forward(static, params, calib)).argmax(-1)
        spec = api.RateEncoding(31, scale=2.0)
        qnet = conversion.convert(static, params, calib, encoding=spec,
                                  weight_bits=8)
        assert qnet.input_scale == pytest.approx(2.0)   # calib max 1.0 * k
        out = api.oracle(qnet, calib, mode="packed")
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(api.oracle(qnet, calib,
                                                   mode="snn")))
        assert (np.asarray(out).argmax(-1) == ref).mean() >= 0.9

    def test_rate_needs_more_steps_than_radix(self):
        """The paper's motivating asymmetry, now measured on *executed*
        nets: radix T=4 (16 levels) beats rate T=4 (5 levels) at matching
        the float reference."""
        static, params, input_hw = lenet.make(pool_mode="avg",
                                              width_mult=0.25)
        calib = jnp.asarray(RNG.uniform(0, 1, (8,) + input_hw), jnp.float32)
        ref = conversion.float_forward(static, params, calib)
        errs = {}
        for name, spec in (("radix", api.RadixEncoding(4)),
                           ("rate", api.RateEncoding(4))):
            qnet = conversion.convert(static, params, calib, encoding=spec,
                                      weight_bits=8)
            out = api.oracle(qnet, calib, mode="packed")
            errs[name] = float(jnp.mean(jnp.abs(out - ref)))
        assert errs["radix"] < errs["rate"]


# ---------------------------------------------------------------------------
# Compile-time validation: no silent fall-throughs.
# ---------------------------------------------------------------------------


class TestCompileValidation:
    def test_backend_and_dataflow_args(self):
        with pytest.raises(ValueError, match="backend"):
            api.Accelerator(backend="xla")
        with pytest.raises(ValueError, match="kernels"):
            api.Accelerator(backend="jnp", dataflow="fused")

    def test_rate_on_kernels_backend_raises(self):
        qnet, hw = _make(pool_mode="avg", encoding=api.RateEncoding(6))
        with pytest.raises(ValueError, match="kernels"):
            api.Accelerator(backend="kernels").compile(qnet, hw)

    def test_unknown_dataflow_raises(self):
        qnet, hw = _make(num_steps=4)
        with pytest.raises(ValueError, match="dataflow"):
            api.Accelerator(dataflow="horner").compile(qnet, hw,
                                                       buckets=(1,))

    def test_mismatched_encoding_override_raises(self):
        qnet, hw = _make(num_steps=4)
        with pytest.raises(ValueError, match="reconvert"):
            api.Accelerator(backend="jnp").compile(
                qnet, hw, encoding=api.RateEncoding(4))
        with pytest.raises(ValueError, match="reconvert"):
            api.oracle(qnet, _x(1, hw), encoding=api.RadixEncoding(5))

    def test_parallel_requires_kernels(self):
        qnet, hw = _make(num_steps=4)
        with pytest.raises(ValueError, match="kernels"):
            api.Accelerator(backend="jnp").compile(qnet, hw, parallel=2)

    def test_oracle_mode_validation(self):
        qnet, hw = _make(num_steps=4)
        with pytest.raises(ValueError, match="mode"):
            api.oracle(qnet, _x(1, hw), mode="spiking")

    def test_item_shape_validation(self):
        qnet, hw = _make(num_steps=4)
        exe = api.Accelerator().compile(qnet, hw, buckets=(1,))
        with pytest.raises(ValueError, match="item shape"):
            exe(np.zeros((1, 8, 8, 1), np.float32))

    def test_facade_emits_no_warnings(self):
        """The supported surface is silent — deprecation noise belongs to
        the shims only (tests/test_api_shims.py)."""
        qnet, hw = _make(num_steps=4)
        x = _x(2, hw)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            api.oracle(qnet, x, mode="packed")
            api.oracle(qnet, x, mode="snn")
            exe = api.Accelerator().compile(qnet, hw, buckets=(2,))
            exe(x)
            api.Accelerator(dataflow="bitserial").compile(
                qnet, hw, buckets=(2,))(x)


# ---------------------------------------------------------------------------
# PlanCache chunking edge cases through Executable.stats() (DESIGN.md §3).
# ---------------------------------------------------------------------------


class TestExecutableStatsEdgeCases:
    def test_non_multiple_of_top_bucket(self):
        """Request sizes that are not a multiple of the top bucket: full
        top chunks plus one bucketed, padded tail — all counted."""
        qnet, hw = _make(num_steps=4)
        exe = api.Accelerator().compile(qnet, hw, buckets=(2, 4)).warmup()
        base = exe.stats()
        x = _x(10, hw)                      # 4 + 4 + tail 2 (bucket 2)
        ref = api.oracle(qnet, x, mode="packed")
        np.testing.assert_array_equal(np.asarray(exe(x)), np.asarray(ref))
        stats = exe.stats()
        assert stats["executions"] - base["executions"] == 3
        assert stats["padded_rows"] == base["padded_rows"]      # 2 fits 2
        assert stats["compiles"] == base["compiles"]
        x = _x(7, hw)                       # 4 + tail 3 -> pad to 4
        np.testing.assert_array_equal(
            np.asarray(exe(x)),
            np.asarray(api.oracle(qnet, x, mode="packed")))
        stats2 = exe.stats()
        assert stats2["executions"] - stats["executions"] == 2
        assert stats2["padded_rows"] - stats["padded_rows"] == 1
        assert stats2["compiles"] == stats["compiles"]

    def test_batch_of_exactly_one(self):
        qnet, hw = _make(num_steps=4)
        exe = api.Accelerator().compile(qnet, hw, buckets=(1, 4))
        x = _x(1, hw)
        ref = api.oracle(qnet, x, mode="packed")
        got = exe(x)
        assert got.shape[0] == 1
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        stats = exe.stats()
        assert stats["executions"] == 1 and stats["padded_rows"] == 0
        assert stats["compiles"] == 1                   # only bucket 1

    def test_mixed_stream_counters(self):
        """Stats across a mixed stream: hits + compiles add up, padding
        accumulates only on non-bucket sizes, zero steady-state
        recompiles."""
        qnet, hw = _make(num_steps=4)
        exe = api.Accelerator().compile(qnet, hw, buckets=(1, 4, 8))
        exe.warmup()
        warm = exe.stats()
        assert warm["compiles"] == 3
        sizes = (1, 3, 8, 2, 6, 13, 1, 7, 4)
        expected_execs = 0
        expected_pad = 0
        for n in sizes:
            chunks, rem = divmod(n, 8)
            if rem == 0:
                chunks, rem = chunks - 1, 8
            bucket = min(b for b in (1, 4, 8) if b >= rem)
            expected_execs += chunks + 1
            expected_pad += bucket - rem
            exe(_x(n, hw))
        stats = exe.stats()
        assert stats["compiles"] == warm["compiles"]    # zero recompiles
        assert (stats["executions"] - warm["executions"]) == expected_execs
        assert (stats["padded_rows"] - warm["padded_rows"]) == expected_pad
        assert stats["hits"] - warm["hits"] == expected_execs


# ---------------------------------------------------------------------------
# Introspection surface.
# ---------------------------------------------------------------------------


class TestIntrospection:
    def test_traffic_kernels_only(self):
        qnet, hw = _make(num_steps=4)
        exe = api.Accelerator().compile(qnet, hw, buckets=(1,))
        t = exe.traffic()
        assert t["traffic_ratio"] >= 3.0
        jexe = api.Accelerator(backend="jnp").compile(qnet, hw,
                                                      buckets=(1,))
        with pytest.raises(NotImplementedError, match="kernels"):
            jexe.traffic()

    def test_memory_report(self):
        qnet, hw = _make(num_steps=4)
        exe = api.Accelerator().compile(qnet, hw, buckets=(1,))
        rep = exe.memory()
        assert rep.total_buffer_bytes > 0
        assert rep.total_param_bytes > 0

    def test_repr_and_props(self):
        qnet, hw = _make(num_steps=4)
        exe = api.Accelerator().compile(qnet, hw, buckets=(4, 1))
        assert exe.buckets == (1, 4)
        assert exe.num_steps == 4
        assert "RadixEncoding" in repr(exe) and "kernels" in repr(exe)
