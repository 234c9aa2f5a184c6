"""Execution engine — the software twin of the accelerator's controller.

Runs a converted :class:`~repro.core.conversion.QuantizedNet` layer by layer,
exactly as the FPGA controller sequences its processing units:

  load activations (ping) -> processing unit -> store activations (pong)

Execution paths
---------------
* ``mode="packed"``  — packed integer levels (uint8).  This is the TPU-native
  path: one tensor per layer, radix packing == integer activation.
* ``mode="snn"``     — paper-faithful spike-plane path: (T, ...) binary
  planes, reduced per layer by the encoding's ``reduce_planes`` (radix:
  Horner; rate: sum).  Bit-exact equal to "packed".
* ``backend="kernels"`` — packed path dispatched through a compiled plan of
  fused-epilogue Pallas kernels (interpret-mode on CPU); ``backend="jnp"``
  uses core/layers.py directly.

The public entry points live in :mod:`repro.api` (``Accelerator.compile``
-> ``Executable``); every path is parameterized by an
:class:`~repro.core.encoding.EncodingSpec`.  :func:`run` and
:func:`compile_plan` survive only as deprecation shims forwarding to the
same implementations.

:func:`_compile_plan_impl` is the controller's program memory: a one-time pass
that pre-pads every weight to block multiples, folds bias + requantization
multiplier into per-layer epilogue row vectors, picks kernel block sizes,
and returns a single jitted closure running the whole network with
activations kept as **packed uint8 levels end-to-end** (DESIGN.md §2) — no
per-call padding, no Python-level layer dispatch, no int32 accumulator ever
leaving a kernel (except the final logits layer).

The engine also produces :class:`MemoryReport` — the ping-pong buffer sizing
and per-layer access counts the paper's memory system is built around (used
by core/hwmodel.py and benchmarks/; reproduces the "4.5 MB BRAM for VGG-11
feature maps" style numbers).
"""

from __future__ import annotations

import dataclasses
import math
import warnings
import weakref
from typing import Callable, List, Literal, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.core import conversion, encoding, layers

__all__ = ["run", "compile_plan", "CompiledPlan", "PlanLayerInfo",
           "PlanCache", "PlanCacheStats", "DEFAULT_BUCKETS",
           "MemoryReport", "memory_report"]


# ---------------------------------------------------------------------------
# Forward execution (the jnp reference paths, parameterized by EncodingSpec).
# ---------------------------------------------------------------------------


def _validate_run_args(mode, backend, method) -> None:
    """Shared run()/facade argument validation — fail loudly, never fall
    through to a silently slower or wrong path."""
    if mode not in ("packed", "snn"):
        raise ValueError(f"mode must be 'packed' or 'snn', got {mode!r}")
    if backend not in ("jnp", "kernels"):
        raise ValueError(
            f"backend must be 'jnp' or 'kernels', got {backend!r}")
    if method not in (None, "bitserial", "fused"):
        raise ValueError(
            f"method must be 'bitserial' or 'fused', got {method!r}")
    if backend == "kernels" and mode == "snn":
        raise ValueError(
            "backend='kernels' executes the packed-level path only; "
            "mode='snn' (spike planes) is the jnp oracle — run it with "
            "backend='jnp'")


def _forward(
    qnet: conversion.QuantizedNet,
    x: jax.Array,
    spec: encoding.EncodingSpec,
    mode: Literal["packed", "snn"] = "packed",
) -> jax.Array:
    """Reference forward on the jnp backend, generic over the encoding.

    ``mode="packed"`` runs integer levels through the quantized twin;
    ``mode="snn"`` runs (T, ...) spike planes — per-plane integer layers
    reduced by ``spec.reduce_planes`` (radix: Horner; rate: plain sum;
    TTFS: weighted one-hot planes; phase: tiled weights / periods).
    Both are bit-exact twins by linearity for any spec whose pools the
    net uses are declared in ``spec.pool_modes``.
    """
    snn = mode == "snn"
    q = spec.quantize(x, qnet.input_scale)
    state = spec.encode(q) if snn else q

    for (kind, cfg), qp in zip(qnet.static, qnet.qlayers):
        if kind == "conv":
            stride, padding = cfg.get("stride", 1), cfg.get("padding", "VALID")
            if snn:
                per = jax.vmap(
                    lambda p, w=qp["w_q"]: layers._int_conv(
                        p, w, stride, padding))(state)
                acc = spec.reduce_planes(per) + qp["b_int"]
            else:
                acc = layers.q_conv2d(state, qp["w_q"], qp["b_int"],
                                      stride=stride, padding=padding)
            state = _requant_or_logits(acc, qp, qnet, spec, snn)
        elif kind == "linear":
            if snn:
                per = jax.vmap(
                    lambda p, w=qp["w_q"]: layers._int_matmul(p, w))(state)
                acc = spec.reduce_planes(per) + qp["b_int"]
            else:
                acc = layers.q_linear(state, qp["w_q"], qp["b_int"])
            state = _requant_or_logits(acc, qp, qnet, spec, snn)
        elif kind == "pool":
            state = _pool(state, cfg, spec, snn)
        elif kind == "flatten":
            if snn:
                state = state.reshape(state.shape[0], state.shape[1], -1)
            else:
                state = state.reshape(state.shape[0], -1)
        else:
            raise ValueError(kind)
    return state  # float logits


def run(
    qnet: conversion.QuantizedNet,
    x: jax.Array,
    *,
    mode: Literal["packed", "snn"] = "packed",
    backend: Literal["jnp", "kernels"] = "jnp",
    method: Optional[Literal["bitserial", "fused"]] = None,
) -> jax.Array:
    """Deprecated shim — use :mod:`repro.api` instead.

    ``repro.api.Accelerator(backend=...).compile(qnet, item_shape)``
    returns an :class:`~repro.api.Executable` for production execution;
    ``repro.api.oracle(qnet, x, mode=...)`` is the un-jitted reference
    (packed or spike-plane).  This shim forwards to the exact same
    implementations the facade uses, so outputs stay bit-identical.
    """
    warnings.warn(
        "repro.core.engine.run() is deprecated; use repro.api.Accelerator"
        ".compile(...) -> Executable (or repro.api.oracle for the "
        "reference paths)", DeprecationWarning, stacklevel=2)
    _validate_run_args(mode, backend, method)
    if backend == "kernels":
        return _cached_plan(qnet, x.shape, method or "fused")(x)
    if method is not None:
        warnings.warn(
            f"method={method!r} selects the in-kernel dataflow and is "
            "ignored with backend='jnp'; pass backend='kernels' to use it",
            UserWarning, stacklevel=2)
    return _forward(qnet, x, qnet.spec, mode)


def _requant_or_logits(acc, qp, qnet, spec, snn):
    if qp["mult"] is None:  # final layer -> float logits
        return acc.astype(jnp.float32) * qnet.logit_scale
    q = spec.requantize(acc, qp["mult"])
    if snn:
        return spec.encode(q)
    return q


def _pool(state, cfg, spec, snn):
    w, pool_mode = cfg["window"], cfg.get("mode", "or")
    if not spec.supports_pool(pool_mode):
        raise ValueError(
            f"{spec.name} encoding does not preserve pool mode "
            f"{pool_mode!r} (supported: {spec.pool_modes})")
    if snn:
        if pool_mode == "or":
            return layers.snn_or_pool(state, w)
        if pool_mode == "avg":
            # per-plane sum pool; planes become multi-bit but stay linear —
            # hardware note: avg mode needs an output requantizer (DESIGN §2)
            return jax.vmap(lambda p: layers.q_avg_pool(p, w))(state)
        if pool_mode == "max":
            if spec.radix_planes:
                # bit-plane-domain lexicographic max (the paper's pooling
                # unit never decodes) — valid whenever planes are the
                # binary expansion of the packed level
                packed = layers.snn_max_pool(state, w)
            else:
                # period-repeated codes (phase, P > 1): decode, pool the
                # packed levels, re-encode
                packed = layers.q_max_pool(
                    spec.decode(state).astype(spec.packed_dtype), w)
            return spec.encode(packed)
        raise ValueError(pool_mode)
    if pool_mode == "or":
        return layers.q_or_pool(state, w)
    if pool_mode == "avg":
        return layers.q_avg_pool(state, w)
    if pool_mode == "max":
        return layers.q_max_pool(state, w)
    raise ValueError(pool_mode)


# ---------------------------------------------------------------------------
# Compiled execution plans — the controller's program memory.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PlanLayerInfo:
    """Per-layer summary + the activation-traffic model (DESIGN.md §2)."""

    name: str
    out_shape: Tuple[int, ...]     # logical (unpadded) output, incl. batch
    out_dtype: str                 # what the plan actually writes
    act_write_bytes: int           # this plan (fused epilogue, packed uint8)
    act_write_bytes_int32: int     # unfused baseline (raw int32 accumulator)


@dataclasses.dataclass
class CompiledPlan:
    """A whole-network jitted closure over pre-padded weights.

    ``plan(x)`` maps float input (the plan's ``input_shape``) to float
    logits, bit-exact equal to ``run(qnet, x, mode="packed",
    backend="jnp")``.  All weight padding / bias+multiplier folding / block
    selection happened at :func:`compile_plan` time; per call there is no
    padding of parameters and no Python-level dispatch (the layer loop is
    unrolled into one XLA program at trace time).

    Every call also runs the plane-occupancy prepass (DESIGN.md §8): the
    number of globally-empty spike planes each kernel layer skipped
    accumulates lazily (a device scalar — no sync until
    :meth:`plane_stats` is read) against the static per-call plane-pass
    budget ``plane_passes_per_call``.
    """

    input_shape: Tuple[int, ...]
    num_steps: int
    method: str
    layers: List[PlanLayerInfo]
    _fn: Callable = dataclasses.field(repr=False)
    _params: list = dataclasses.field(repr=False)
    data_parallel: int = 1         # batch shards (shard_map over devices)
    plane_passes_per_call: int = 0  # static: sum of in_bits*periods/layer
    _skipped: Optional[jax.Array] = dataclasses.field(default=None,
                                                      repr=False)
    _calls: int = dataclasses.field(default=0, repr=False)
    tuned_tiles: List[dict] = dataclasses.field(default_factory=list)
    """Per kernel layer: the resolved execution strategy — layer name +
    the :class:`~repro.kernels.autotune.KernelConfig` fields (impl, MXU
    dot lowering, tile shapes, plane-parallel flag) and whether it came
    from an autotune sweep or is the untuned default."""

    def __call__(self, x: jax.Array) -> jax.Array:
        out, skipped = self._fn(self._params, x)
        # lazy device-side accumulation: no host sync on the hot path.
        # Under an outer jax transformation `skipped` is a tracer — storing
        # it would leak it (and poison later eager calls), so the counters
        # simply don't accumulate for traced calls; the plan stays pure.
        if not isinstance(skipped, jax.core.Tracer):
            self._skipped = skipped if self._skipped is None \
                else self._skipped + skipped
            self._calls += 1
        return out

    def plane_stats(self) -> dict:
        """Sparsity-prepass counters: plane passes skipped (all-zero
        spike planes — bitserial early-exits, fused masked lanes) vs the
        static schedule total across every call so far.  Reading this
        syncs the lazily-accumulated device scalar."""
        skipped = 0 if self._skipped is None else int(
            np.asarray(self._skipped).sum())
        return {"plane_passes_skipped": skipped,
                "plane_passes_total": self._calls * self.plane_passes_per_call}

    def reset_plane_stats(self) -> None:
        """Zero the sparsity counters (warmup runs all-zero batches that
        skip nearly every plane — left in, they would swamp the stats of
        real traffic)."""
        self._skipped = None
        self._calls = 0

    def activation_traffic(self) -> dict:
        """Modeled inter-layer activation bytes written: fused vs unfused."""
        fused = sum(l.act_write_bytes for l in self.layers)
        unfused = sum(l.act_write_bytes_int32 for l in self.layers)
        return {
            "layers": [dataclasses.asdict(l) for l in self.layers],
            "fused_write_bytes": fused,
            "int32_write_bytes": unfused,
            "traffic_ratio": unfused / max(fused, 1),
        }


def compile_plan(
    qnet: conversion.QuantizedNet,
    input_shape: Tuple[int, ...],
    *,
    method: Literal["bitserial", "fused"] = "fused",
    data_parallel: int = 1,
) -> CompiledPlan:
    """Deprecated shim — use :mod:`repro.api` instead.

    ``repro.api.Accelerator(dataflow=method).compile(qnet, item_shape,
    buckets=(batch,))`` returns an :class:`~repro.api.Executable` whose
    per-bucket plans are built by the exact implementation this shim
    forwards to, so plans stay bit-identical.
    """
    warnings.warn(
        "repro.core.engine.compile_plan() is deprecated; use repro.api."
        "Accelerator.compile(...) -> Executable", DeprecationWarning,
        stacklevel=2)
    return _compile_plan_impl(qnet, input_shape, method=method,
                              data_parallel=data_parallel)


def _compile_plan_impl(
    qnet: conversion.QuantizedNet,
    input_shape: Tuple[int, ...],
    *,
    method: Optional[str] = "fused",
    data_parallel: int = 1,
    spec: Optional[encoding.EncodingSpec] = None,
    autotune: bool = False,
) -> CompiledPlan:
    """Compile ``qnet`` into a single jitted fused-epilogue kernel pipeline.

    One-time work (per (net, input shape)):

    * weights pre-padded to kernel block multiples — conv in-channels to the
      previous layer's padded out-channels, so activations stay physically
      channel-padded between layers and are never re-padded per call;
    * bias + requantization multiplier folded into per-layer epilogue row
      vectors (padding lanes get ``mult = 0`` -> level 0, keeping the pad
      lanes algebraically inert through pools and later layers);
    * the linear layer following ``flatten`` gets its weight rows scattered
      to the padded-channel flattened layout (the one re-indexing that
      replaces all runtime gather/slice work);
    * block sizes chosen per layer; the avg-pool carry (activations
      temporarily wider than T bits, division folded into the next
      multiplier) tracked so bit-serial extraction stays exact;
    * the encoding's declared :class:`~repro.core.encoding.KernelSchedule`
      threaded into every kernel call (packed bit count, period replays,
      epilogue clip level and output grid — TTFS's "pow2" re-timing runs
      in-kernel).

    Every compiled layer also runs the **plane-occupancy prepass**
    (DESIGN.md §8): one bitwise-OR reduction over the layer's packed
    input finds spike planes no activation uses, the kernels skip them
    (bitserial ``lax.cond`` early-exit) or mask them (fused bit-mask) —
    bit-exact either way — and the per-call skip count surfaces through
    ``CompiledPlan.plane_stats()`` / ``Executable.stats()``.

    The returned plan keeps every inter-layer activation as packed uint8
    levels (1 byte/element — the pong buffer's T-bit format) except where a
    sum-pool carry exceeds 8 bits; only the final logits layer emits a raw
    int32 accumulator.

    ``data_parallel=k`` (k > 1) compiles the plan for a per-device batch of
    ``input_shape[0] / k`` and wraps it in a ``shard_map`` over the batch
    axis (weights replicated, activations batch-sharded) — the serving
    stack's scale-out lever (DESIGN.md §3).  Bit-exact equal to the
    single-device plan.

    ``autotune=True`` resolves each kernel layer's execution strategy
    (:class:`~repro.kernels.autotune.KernelConfig`: Pallas tile shapes /
    MXU dot lowering / plane-parallel grid, or the jitted XLA twin) by
    timing the legal candidates on representative random activations at
    plan-compile time — tuning cannot happen inside the jit trace, so it
    runs eagerly here and the winning strategy is baked into the layer
    closure.  Winners are cached per problem key (process + on-disk
    table), so recompiles and other plans reuse them; the chosen
    strategies surface as ``CompiledPlan.tuned_tiles`` →
    ``Executable.stats()["autotune"]``.  Every candidate is bit-exact
    (non-default dot lowerings are only legal when
    ``autotune.exact_lowering`` proves them so), so this knob never
    changes results.
    """
    spec = spec if spec is not None else qnet.spec
    method = spec.validate_dataflow(method)  # kernels-capable specs only
    if data_parallel < 1:
        raise ValueError(f"data_parallel must be >= 1, got {data_parallel}")
    if data_parallel > 1:
        return _data_parallel_plan(qnet, input_shape, method, data_parallel,
                                   spec, autotune=autotune)
    from repro.kernels import autotune as autotune_mod   # deferred:
    from repro.kernels import ops as kops                # optional path
    from repro.kernels.autotune import KernelConfig
    from repro.kernels.radix_conv import radix_conv2d_pallas
    from repro.kernels.radix_matmul import radix_matmul_pallas

    # The spec's declared KernelSchedule is everything the kernels need:
    # T is the *packed* bit count (== num_steps except for period-repeated
    # codes: phase packs one K-phase period per byte); `periods` replays
    # the tiled plane-weight schedule in the bitserial dataflow (kernels
    # divide the accumulator back down, exactly); `out_level`/`out_grid`
    # parameterize the fused epilogue's requantization grid (TTFS: "pow2",
    # the in-kernel log-spaced re-timing of the single output spike).
    sched = spec.kernel_schedule()
    T = sched.packed_bits
    periods = sched.periods
    out_grid = sched.out_grid
    if spec.max_level > 255:
        raise ValueError(
            f"packed uint8 plans require <= 256 levels, got {spec.levels} "
            f"({spec.name}, T={T})")
    interp = kops._interpret()

    if len(input_shape) == 4:
        batch, h, w, c_real = input_shape
        c_pad = c_real
    elif len(input_shape) == 2:
        batch, f_real = input_shape
        f_pad = f_real
        h = w = c_real = c_pad = None
    else:
        raise ValueError(f"input_shape must be NHWC or NF, got {input_shape}")
    scatter: Optional[Tuple[int, int, int]] = None  # (spatial, c_real, c_pad)

    rows = batch                   # current physical row count (batch dim)
    bits = T                       # integer bits carried by activations
    steps: List[Tuple[Callable, dict]] = []
    infos: List[PlanLayerInfo] = []
    tuned: List[dict] = []
    n_layers = len(qnet.static)
    total_passes = 0               # static plane-pass budget (all layers)
    tune_rng = np.random.default_rng(0)   # representative tuning inputs

    def _elems(shape) -> int:
        return int(np.prod(shape))

    def _resolve_cfg(name, key_fn, cand_fn, build):
        """One layer's execution strategy: a tuned winner (the sweep runs
        HERE, eagerly — candidates cannot be timed inside the jit trace;
        cached winners make recompiles instant) or the untuned default.
        The choice is recorded in ``tuned_tiles`` either way."""
        if autotune:
            kcfg = autotune_mod.tune(key_fn(), cand_fn(), build)
        else:
            kcfg = KernelConfig()
        tuned.append({"layer": name, "tuned": bool(autotune),
                      **kcfg.as_dict()})
        return kcfg

    def _tune_sample(shape, nbits):
        """Random packed levels standing in for this layer's activations
        during the timing sweep (uniform over the level range — every
        plane occupied, so sweeps don't overfit to sparsity luck)."""
        dt = np.uint8 if nbits <= 8 else np.int32
        return jnp.asarray(tune_rng.integers(0, 1 << nbits, shape, dtype=dt))

    def _occ(state, in_bits):
        """Plane-occupancy prepass (DESIGN.md §8): one bitwise-OR
        reduction over the layer's packed input; returns the kernel's
        occupancy row and the number of plane passes it will skip
        (bitserial) or mask (fused) — all-zero spike planes only, so the
        gated kernels stay bit-exact."""
        row, occ_bits = kops.plane_occupancy(state, in_bits)
        return row, (in_bits - occ_bits.sum()) * periods

    for (kind, cfg), qp in zip(qnet.static, qnet.qlayers):
        if kind == "conv":
            kh, kw, cin, cout = qp["w_q"].shape
            assert cin == c_real, (cin, c_real)
            stride = cfg.get("stride", 1)
            pads = None
            if cfg.get("padding", "VALID") == "SAME":
                pads = ((0, 0), kops.same_pads(h, kh, stride),
                        kops.same_pads(w, kw, stride), (0, 0))
            hp = h + (pads[1][0] + pads[1][1] if pads else 0)
            wp = w + (pads[2][0] + pads[2][1] if pads else 0)
            in_shape_phys = (batch, h, w, c_pad)   # this layer's input
            in_bits = bits
            h = (hp - kh) // stride + 1
            w = (wp - kw) // stride + 1
            w_cin = jnp.pad(qp["w_q"],
                            ((0, 0), (0, 0), (0, c_pad - cin), (0, 0)))
            last = qp["mult"] is None
            name = f"conv{kh}x{kw}x{cin}->{cout}" + (f"/s{stride}"
                                                     if stride > 1 else "")

            def build_conv(kcfg, *, pads=pads, stride=stride, in_bits=in_bits,
                           cout=cout, w_cin=w_cin, qp=qp, last=last):
                """(out_channels, params, apply) for one conv strategy.

                The XLA twin keeps out-channels unpadded (the backend
                compiler needs no alignment — downstream layers fold
                whatever physical channel count they're handed); the
                Pallas path pads to the config's bco multiple."""
                if kcfg.impl == "xla":
                    if last:
                        p = {"w": w_cin,
                             "b": jnp.asarray(qp["b_int"], jnp.int32)}

                        def apply(state, p, *, kcfg=kcfg):
                            if pads is not None:
                                state = jnp.pad(state, pads)
                            occ, skipped = _occ(state, in_bits)
                            acc = kops._xla_conv2d(
                                state, p["w"], None, None, occ,
                                num_steps=in_bits, method=method,
                                stride=stride, periods=periods,
                                mxu_dtype=kcfg.mxu_dtype)
                            return acc + p["b"], skipped
                        return cout, p, apply
                    bias_row, mult_row = kops.epilogue_rows(
                        qp["b_int"], qp["mult"], cout, cout, encoding=spec)
                    p = {"w": w_cin, "bias": bias_row, "mult": mult_row}

                    def apply(state, p, *, kcfg=kcfg):
                        if pads is not None:
                            state = jnp.pad(state, pads)
                        occ, skipped = _occ(state, in_bits)
                        return kops._xla_conv2d(
                            state, p["w"], p["bias"], p["mult"], occ,
                            num_steps=in_bits, method=method, stride=stride,
                            periods=periods, mxu_dtype=kcfg.mxu_dtype,
                            out_level=sched.out_level,
                            out_grid=out_grid), skipped
                    return cout, p, apply

                cop, bco = kops._block(cout, pref=kcfg.bco)
                w_p = jnp.pad(w_cin, ((0, 0), (0, 0), (0, 0),
                                      (0, cop - cout)))
                pp = kcfg.plane_parallel and method == "bitserial"
                if last:
                    p = {"w": w_p, "b": jnp.asarray(qp["b_int"], jnp.int32)}

                    def apply(state, p, *, bco=bco, kcfg=kcfg, pp=pp):
                        if pads is not None:
                            state = jnp.pad(state, pads)
                        occ, skipped = _occ(state, in_bits)
                        acc = radix_conv2d_pallas(
                            state, p["w"], num_steps=in_bits, method=method,
                            bco=bco, stride=stride, interpret=interp,
                            periods=periods, occupancy=occ,
                            mxu_dtype=kcfg.mxu_dtype, plane_parallel=pp,
                        )[..., :cout]
                        return acc + p["b"], skipped
                    return cop, p, apply
                bias_row, mult_row = kops.epilogue_rows(
                    qp["b_int"], qp["mult"], cout, cop, encoding=spec)
                p = {"w": w_p, "bias": bias_row, "mult": mult_row}

                def apply(state, p, *, bco=bco, kcfg=kcfg, pp=pp):
                    if pads is not None:
                        state = jnp.pad(state, pads)
                    occ, skipped = _occ(state, in_bits)
                    return radix_conv2d_pallas(
                        state, p["w"], num_steps=in_bits, method=method,
                        bco=bco, stride=stride, interpret=interp,
                        periods=periods, occupancy=occ,
                        bias=p["bias"], mult=p["mult"], out_steps=T,
                        out_level=sched.out_level, out_grid=out_grid,
                        mxu_dtype=kcfg.mxu_dtype, plane_parallel=pp,
                    ), skipped
                return cop, p, apply

            layer_sched = encoding.KernelSchedule(
                packed_bits=in_bits, periods=periods, out_grid=out_grid)
            sample = _tune_sample(in_shape_phys, in_bits) if autotune \
                else None

            def _build_thunk(c, *, build_conv=build_conv, sample=sample):
                _, p_c, a_c = build_conv(c)
                return lambda: a_c(sample, p_c)[0]

            kcfg = _resolve_cfg(
                name,
                lambda hp=hp, wp=wp, c_pad=c_pad: autotune_mod.conv_key(
                    hp, wp, c_pad, kh, kw, cout, stride, layer_sched,
                    method, batch=batch, epilogue=not last, sparsity=True),
                lambda hp=hp, wp=wp, c_pad=c_pad: autotune_mod.conv_candidates(
                    hp, wp, c_pad, kh, kw, cout, layer_sched, method,
                    interpret=interp, act_dtypes=("u8",)),
                _build_thunk)
            cop, p, apply = build_conv(kcfg)

            total_passes += bits * periods
            steps.append((apply, p))
            out_shape = (batch, h, w, cout)
            infos.append(PlanLayerInfo(
                name=name,
                out_shape=out_shape,
                out_dtype="int32" if last else "uint8",
                act_write_bytes=_elems(out_shape) * (4 if last else 1),
                act_write_bytes_int32=_elems(out_shape) * 4,
            ))
            c_real, c_pad, bits = cout, cop, T

        elif kind == "linear":
            fin, fout = qp["w_q"].shape
            assert fin == f_real, (fin, f_real)
            w_q = qp["w_q"]
            # rows up to the physically padded feature count (zeros: the
            # extra activation lanes are level 0 by construction).  After a
            # flatten of channel-padded maps the zeros interleave per
            # spatial position -> scatter via reshape, not an end-pad.
            if scatter is not None:
                spatial, cr, cp = scatter
                w_q = jnp.pad(w_q.reshape(spatial, cr, fout),
                              ((0, 0), (0, cp - cr), (0, 0))
                              ).reshape(spatial * cp, fout)
                scatter = None
            elif f_pad > fin:
                w_q = jnp.pad(w_q, ((0, f_pad - fin), (0, 0)))
            last = qp["mult"] is None
            in_bits = bits
            name = f"linear{fin}->{fout}"

            def build_linear(kcfg, *, w_q=w_q, qp=qp, last=last,
                             in_bits=in_bits, fout=fout, rows=rows,
                             f_pad=f_pad):
                """(padded_fout, padded_rows, params, apply) for one
                strategy.  XLA keeps everything unpadded; Pallas pads
                rows/contraction/output to the config's tile multiples."""
                if kcfg.impl == "xla":
                    if last:
                        p = {"w": w_q,
                             "b": jnp.asarray(qp["b_int"], jnp.int32)}

                        def apply(state, p, *, kcfg=kcfg):
                            occ, skipped = _occ(state, in_bits)
                            acc = kops._xla_matmul(
                                state, p["w"], None, None, occ,
                                num_steps=in_bits, method=method,
                                periods=periods,
                                mxu_dtype=kcfg.mxu_dtype)[:batch]
                            return acc + p["b"], skipped
                        return fout, rows, p, apply
                    bias_row, mult_row = kops.epilogue_rows(
                        qp["b_int"], qp["mult"], fout, fout, encoding=spec)
                    p = {"w": w_q, "bias": bias_row, "mult": mult_row}

                    def apply(state, p, *, kcfg=kcfg):
                        occ, skipped = _occ(state, in_bits)
                        return kops._xla_matmul(
                            state, p["w"], p["bias"], p["mult"], occ,
                            num_steps=in_bits, method=method,
                            periods=periods, mxu_dtype=kcfg.mxu_dtype,
                            out_level=sched.out_level,
                            out_grid=out_grid), skipped
                    return fout, rows, p, apply

                mp, bm = kops._block(rows, pref=kcfg.bm)
                kp, bk = kops._block(f_pad, pref=kcfg.bk)
                np_, bn = kops._block(fout, pref=kcfg.bn)
                w_p = jnp.pad(w_q, ((0, kp - f_pad), (0, np_ - fout)))
                row_pad = mp - rows
                col_pad = kp - f_pad
                pp = kcfg.plane_parallel and method == "bitserial"
                if last:
                    p = {"w": w_p, "b": jnp.asarray(qp["b_int"], jnp.int32)}

                    def apply(state, p, *, bm=bm, bk=bk, bn=bn, pp=pp,
                              row_pad=row_pad, col_pad=col_pad, kcfg=kcfg):
                        if row_pad or col_pad:
                            state = jnp.pad(state,
                                            ((0, row_pad), (0, col_pad)))
                        occ, skipped = _occ(state, in_bits)
                        acc = radix_matmul_pallas(
                            state, p["w"], num_steps=in_bits, method=method,
                            bm=bm, bk=bk, bn=bn, interpret=interp,
                            periods=periods, occupancy=occ,
                            mxu_dtype=kcfg.mxu_dtype, plane_parallel=pp,
                        )[:batch, :fout]
                        return acc + p["b"], skipped
                    return np_, mp, p, apply
                bias_row, mult_row = kops.epilogue_rows(
                    qp["b_int"], qp["mult"], fout, np_, encoding=spec)
                p = {"w": w_p, "bias": bias_row, "mult": mult_row}

                def apply(state, p, *, bm=bm, bk=bk, bn=bn, pp=pp,
                          row_pad=row_pad, col_pad=col_pad, kcfg=kcfg):
                    if row_pad or col_pad:
                        state = jnp.pad(state, ((0, row_pad), (0, col_pad)))
                    occ, skipped = _occ(state, in_bits)
                    return radix_matmul_pallas(
                        state, p["w"], num_steps=in_bits, method=method,
                        bm=bm, bk=bk, bn=bn, interpret=interp,
                        periods=periods, occupancy=occ,
                        bias=p["bias"], mult=p["mult"], out_steps=T,
                        out_level=sched.out_level, out_grid=out_grid,
                        mxu_dtype=kcfg.mxu_dtype, plane_parallel=pp,
                    ), skipped
                return np_, mp, p, apply

            layer_sched = encoding.KernelSchedule(
                packed_bits=in_bits, periods=periods, out_grid=out_grid)
            sample = _tune_sample((rows, f_pad), in_bits) if autotune \
                else None

            def _build_thunk(c, *, build_linear=build_linear, sample=sample):
                _, _, p_c, a_c = build_linear(c)
                return lambda: a_c(sample, p_c)[0]

            kcfg = _resolve_cfg(
                name,
                lambda rows=rows, f_pad=f_pad: autotune_mod.matmul_key(
                    rows, f_pad, fout, layer_sched, method,
                    epilogue=not last, sparsity=True),
                lambda rows=rows, f_pad=f_pad: autotune_mod.matmul_candidates(
                    rows, f_pad, fout, layer_sched, method,
                    interpret=interp, act_dtypes=("u8",)),
                _build_thunk)
            np_, mp, p, apply = build_linear(kcfg)

            total_passes += bits * periods
            steps.append((apply, p))
            out_shape = (batch, fout)
            infos.append(PlanLayerInfo(
                name=name,
                out_shape=out_shape,
                out_dtype="int32" if last else "uint8",
                act_write_bytes=_elems(out_shape) * (4 if last else 1),
                act_write_bytes_int32=_elems(out_shape) * 4,
            ))
            f_real, f_pad, bits = fout, np_, T
            rows = mp if not last else batch

        elif kind == "pool":
            window, pool_mode = cfg["window"], cfg.get("mode", "or")
            h, w = h // window, w // window
            if pool_mode == "avg":
                # sum-pool widens the carry; stays packed while it fits a byte
                bits = layers.sum_pool_bits(bits, window)
                packed = bits <= 8

                def apply(state, p, *, window=window, packed=packed):
                    out = layers.q_avg_pool(state, window)
                    out = out.astype(jnp.uint8) if packed else out
                    return out, jnp.int32(0)
            elif pool_mode in ("or", "max"):
                fn = (layers.q_or_pool if pool_mode == "or"
                      else layers.q_max_pool)

                def apply(state, p, *, fn=fn, window=window):
                    return fn(state, window), jnp.int32(0)
            else:
                raise ValueError(pool_mode)
            steps.append((apply, {}))
            out_shape = (batch, h, w, c_real)
            nbytes = 1 if bits <= 8 else 4
            infos.append(PlanLayerInfo(
                name=f"pool{window}/{pool_mode}",
                out_shape=out_shape,
                out_dtype="uint8" if nbytes == 1 else "int32",
                act_write_bytes=_elems(out_shape) * nbytes,
                act_write_bytes_int32=_elems(out_shape) * 4,
            ))

        elif kind == "flatten":
            steps.append((lambda state, p: (
                state.reshape(state.shape[0], -1), jnp.int32(0)), {}))
            # the padded-channel layout becomes the padded feature layout;
            # the NEXT linear scatters its weight rows to match (plan-time)
            f_real = h * w * c_real
            f_pad = h * w * c_pad
            if c_pad > c_real:
                scatter = (h * w, c_real, c_pad)
        else:
            raise ValueError(kind)

    # plain locals, NOT qnet attribute reads: the jitted closure must not
    # strongly reference the net, or the plan cache's weakref never dies
    input_scale, logit_scale = qnet.input_scale, qnet.logit_scale

    def forward(params, x):
        state = spec.quantize(x, input_scale)
        skipped = jnp.zeros((1,), jnp.int32)   # (1,): shard_map-concatable
        for (apply, _), p in zip(steps, params):
            state, sk = apply(state, p)
            skipped = skipped + sk
        return state.astype(jnp.float32) * logit_scale, skipped

    params = [p for _, p in steps]
    return CompiledPlan(
        input_shape=tuple(input_shape),
        num_steps=T,
        method=method,
        layers=infos,
        _fn=jax.jit(forward),
        _params=params,
        plane_passes_per_call=total_passes,
        tuned_tiles=tuned,
    )


# plan cache: keyed by a weakref to the net + call signature.  The weakref
# IS the identity component: two refs compare equal only while both resolve
# to the same live net (a dead ref never equals a live one), so a GC'd
# net's recycled id() can never alias a stale entry — unlike the previous
# (id(qnet), ...) keys, where aliasing was only caught by a lookup-time
# liveness guard.  ``QuantizedNet`` uses identity hashing (eq=False) to
# make its weakrefs hashable.
_PLAN_CACHE: dict = {}


def _cache_key(qnet, *rest) -> tuple:
    return (weakref.ref(qnet),) + rest


def _weakref_cache_get(cache: dict, key, qnet) -> Optional[CompiledPlan]:
    """Live-entry lookup (belt-and-braces: re-check the referent)."""
    hit = cache.get(key)
    if hit is not None and hit[0]() is qnet:
        return hit[1]
    return None


def _weakref_cache_prune(cache: dict) -> int:
    """Drop entries whose net died (their plans pin padded weights +
    jitted executables); returns the number dropped."""
    stale = [k for k, (r, _) in cache.items() if r() is None]
    for k in stale:
        del cache[k]
    return len(stale)


def _cached_plan(qnet, input_shape, method) -> CompiledPlan:
    key = _cache_key(qnet, tuple(input_shape), method)
    plan = _weakref_cache_get(_PLAN_CACHE, key, qnet)
    if plan is not None:
        return plan
    _weakref_cache_prune(_PLAN_CACHE)
    plan = _compile_plan_impl(qnet, input_shape, method=method)
    _PLAN_CACHE[key] = (weakref.ref(qnet), plan)
    return plan


def _data_parallel_plan(qnet, input_shape, method, data_parallel, spec=None,
                        autotune=False):
    """shard_map a per-device plan over the batch axis (DESIGN.md §3)."""
    from jax.sharding import PartitionSpec as P

    batch = int(input_shape[0])
    ndev = len(jax.devices())
    if batch % data_parallel:
        raise ValueError(
            f"batch {batch} not divisible by data_parallel={data_parallel}")
    if data_parallel > ndev:
        raise ValueError(
            f"data_parallel={data_parallel} exceeds {ndev} visible devices")
    inner = _compile_plan_impl(
        qnet, (batch // data_parallel,) + tuple(input_shape[1:]),
        method=method, spec=spec, autotune=autotune)
    mesh = compat.make_mesh((data_parallel,), ("batch",))
    # weights replicated, input/output sharded along batch (the logits AND
    # the per-shard skip counters — each shard ran its own prepass); no
    # collectives cross shards, so replication checking is moot (and trips
    # over pallas_call on some jax versions) -> disabled.
    fn = jax.shard_map(inner._fn, mesh=mesh,
                      in_specs=(P(), P("batch")),
                      out_specs=(P("batch"), P("batch")),
                      check_vma=False)
    infos = [dataclasses.replace(
        l,
        out_shape=(l.out_shape[0] * data_parallel,) + l.out_shape[1:],
        act_write_bytes=l.act_write_bytes * data_parallel,
        act_write_bytes_int32=l.act_write_bytes_int32 * data_parallel,
    ) for l in inner.layers]
    return CompiledPlan(
        input_shape=tuple(input_shape),
        num_steps=inner.num_steps,
        method=method,
        layers=infos,
        _fn=jax.jit(fn),
        _params=inner._params,
        data_parallel=data_parallel,
        plane_passes_per_call=inner.plane_passes_per_call * data_parallel,
        tuned_tiles=inner.tuned_tiles,
    )


# ---------------------------------------------------------------------------
# Batch-bucketing plan cache — the serving hot path (DESIGN.md §3).
# ---------------------------------------------------------------------------


DEFAULT_BUCKETS: Tuple[int, ...] = (1, 8, 32, 128)


@dataclasses.dataclass
class PlanCacheStats:
    """Counters proving steady-state serving never recompiles."""

    hits: int = 0            # plan served from cache
    compiles: int = 0        # compile_plan invocations (cache misses)
    pruned: int = 0          # entries dropped after their net was GC'd
    executions: int = 0      # plan calls (chunks count individually)
    padded_rows: int = 0     # bucket-padding rows executed and sliced off
    failures: int = 0        # run() calls that raised (build or execute)

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class PlanCache:
    """Batch-bucketing compiled-plan cache (wrapped by ``api.Executable``).

    A serving deployment sees arbitrary request batch sizes; compiling one
    plan per size would make every novel size a multi-second stall.  The
    cache instead pre-compiles plans for a fixed ascending **bucket ladder**
    (paper-twin reading: the controller's program memory holds a few batch
    programs, not one per request).  A request of ``n`` images

    * pads up to the smallest bucket ``>= n`` (zero rows — sliced off after
      the call, and junk lanes never escape: the plan's final slice keeps
      logits rows ``[:bucket]`` and the pad rows are discarded here),
    * or, when ``n`` exceeds the top bucket, chunks into top-bucket pieces
      plus one bucketed tail.

    Plans are keyed by (weakref(net), bucket, item shape, method, encoding)
    — the weakref is the identity component, so entries die with the
    ``QuantizedNet`` and recycled ``id()``s can never alias — and
    ``data_parallel`` shards each bucket over the visible devices when it
    divides evenly (``gcd(bucket, n_devices)`` shards; single-device
    buckets — e.g. bucket 1 — fall back transparently).

    ``stats`` counts hits/compiles/executions/padding so tests and the
    serving loop can assert zero steady-state recompiles.
    """

    def __init__(
        self,
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        *,
        method: Literal["bitserial", "fused"] = "fused",
        data_parallel: Optional[int] = None,
        encoding: Optional["encoding.EncodingSpec"] = None,
        compile_fn: Optional[Callable] = None,
        autotune: bool = False,
    ):
        bs = tuple(sorted({int(b) for b in buckets}))
        if not bs or bs[0] < 1:
            raise ValueError(f"bucket ladder must be positive, got {buckets}")
        if data_parallel is not None and data_parallel < 1:
            raise ValueError(
                f"data_parallel must be >= 1 (or None for auto), got "
                f"{data_parallel}")
        self.buckets = bs
        self.method = method
        self.data_parallel = data_parallel   # None -> auto (gcd with devices)
        self.encoding = encoding             # None -> the net's own spec
        # compile_fn(qnet, input_shape) -> callable overrides the default
        # fused-kernel plan builder; repro.api uses it for the jnp backend
        # (per-bucket jitted closures share the bucketing/chunking/stats
        # machinery with kernel plans).
        self._compile_fn = compile_fn
        self.autotune = bool(autotune)   # sweep kernel configs at compile
        self.stats = PlanCacheStats()
        self._plans: dict = {}   # key -> (weakref(qnet), plan callable)

    def __len__(self) -> int:
        return len(self._plans)

    def bucket_for(self, n: int) -> int:
        """Smallest bucket >= n (top bucket for oversize chunk tails)."""
        if n < 1:
            raise ValueError(f"batch size must be >= 1, got {n}")
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    def prune(self) -> int:
        """Drop entries whose ``QuantizedNet`` was garbage-collected.  Runs
        automatically on every cache miss; returns the number dropped."""
        n = _weakref_cache_prune(self._plans)
        self.stats.pruned += n
        return n

    def plane_stats(self) -> dict:
        """Sparsity-prepass counters summed over every live cached plan
        (DESIGN.md §8): ``plane_passes_skipped`` (all-zero spike planes
        the kernels early-exited / masked) vs ``plane_passes_total`` (the
        static schedule budget across all executions).  Zeros for plans
        without the prepass (the jnp-backend closures)."""
        out = {"plane_passes_skipped": 0, "plane_passes_total": 0}
        for _, plan in self._plans.values():
            getter = getattr(plan, "plane_stats", None)
            if getter is not None:
                for k, v in getter().items():
                    out[k] += v
        return out

    def tuned_tiles(self) -> List[dict]:
        """Per-layer kernel strategies of every live cached plan, one row
        per (bucket, layer): the layer name, whether a timed sweep picked
        the strategy (``tuned``) or it is the untuned default, and the
        winning :class:`~repro.kernels.autotune.KernelConfig` fields.
        Empty for jnp-backend closures (no kernel strategies to pick)."""
        out: List[dict] = []
        for key, (_, plan) in self._plans.items():
            for row in getattr(plan, "tuned_tiles", None) or []:
                out.append({"bucket": key[1], **row})
        return out

    def _shards_for(self, bucket: int) -> int:
        avail = len(jax.devices())
        want = avail if self.data_parallel is None else min(
            self.data_parallel, avail)
        return math.gcd(bucket, want)

    def plan_for(self, qnet: conversion.QuantizedNet, bucket: int,
                 item_shape: Tuple[int, ...]) -> CompiledPlan:
        """Cached plan for one bucket (compiles on first use)."""
        key = _cache_key(qnet, int(bucket), tuple(item_shape),
                         self.method, self.encoding)
        plan = _weakref_cache_get(self._plans, key, qnet)
        if plan is not None:
            self.stats.hits += 1
            return plan
        self.prune()
        shape = (int(bucket),) + tuple(item_shape)
        if self._compile_fn is not None:
            plan = self._compile_fn(qnet, shape)
        else:
            plan = _compile_plan_impl(
                qnet, shape, method=self.method,
                data_parallel=self._shards_for(int(bucket)),
                spec=self.encoding, autotune=self.autotune)
        self._plans[key] = (weakref.ref(qnet), plan)
        self.stats.compiles += 1
        return plan

    def warmup(self, qnet: conversion.QuantizedNet,
               item_shape: Tuple[int, ...]) -> List[CompiledPlan]:
        """Pre-compile the whole ladder so serving never compiles on the
        hot path.  Each plan is also executed once on zeros: building a
        plan pads weights and folds epilogues, but the jitted closure
        itself XLA-compiles on first call — without this, the first
        request per bucket would still pay the compile stall."""
        plans = [self.plan_for(qnet, b, item_shape) for b in self.buckets]
        for b, plan in zip(self.buckets, plans):
            x0 = jnp.zeros((b,) + tuple(item_shape), jnp.float32)
            jax.block_until_ready(plan(x0))
            reset = getattr(plan, "reset_plane_stats", None)
            if reset is not None:
                # the all-zero warmup batch skips nearly every plane;
                # keep the sparsity counters about real traffic
                reset()
        return plans

    def run(self, qnet: conversion.QuantizedNet, x: jax.Array) -> jax.Array:
        """Arbitrary-batch inference: pad to the nearest bucket / chunk by
        the top bucket, slice the logits back to the request size.

        A raised plan build/execution error increments ``stats.failures``
        before propagating — the serving layer's fault-recovery path
        (DESIGN.md §3) reconciles its retry/quarantine counters against
        it."""
        try:
            return self._run(qnet, x)
        except Exception:
            self.stats.failures += 1
            raise

    def _run(self, qnet: conversion.QuantizedNet, x: jax.Array) -> jax.Array:
        n = x.shape[0]
        item = tuple(x.shape[1:])
        top = self.buckets[-1]
        outs = []
        off = 0
        while n - off > top:                     # oversize: full top chunks
            outs.append(self.plan_for(qnet, top, item)(x[off:off + top]))
            self.stats.executions += 1
            off += top
        rem = n - off
        bucket = self.bucket_for(rem)
        tail = x[off:]
        if bucket > rem:
            tail = jnp.pad(tail, ((0, bucket - rem),) + ((0, 0),) * len(item))
            self.stats.padded_rows += bucket - rem
        outs.append(self.plan_for(qnet, bucket, item)(tail)[:rem])
        self.stats.executions += 1
        if len(outs) == 1:
            return outs[0]
        # chunk logits may carry different shardings (per-bucket
        # data_parallel differs) -> gather to one device to concatenate
        dev0 = jax.devices()[0]
        return jnp.concatenate([jax.device_put(o, dev0) for o in outs],
                               axis=0)


class LMPlanCache:
    """Sequence-bucketed plan cache for autoregressive LM serving — the
    KV-cache analog of :class:`PlanCache` (wrapped by ``api.LMExecutable``).

    Decode serving has two plan families instead of one batch ladder:

    * per-sequence-bucket **prefill** plans — prompts right-pad to the
      smallest bucket ``>= S0`` and the model gathers last-token logits at
      the true length (``model.prefill(..., true_len=)``), so every prompt
      length in a bucket traces ONE plan;
    * ONE **decode-step** plan reused for every generated token — the KV
      cache shapes and the ``(B, 1)`` token shape are position-independent,
      so autoregression never recompiles.

    Plans are built once by the injected builders and cached; ``stats``
    reuses :class:`PlanCacheStats` (``padded_rows`` here counts padded
    prompt columns plus padded batch rows), so LM serving tests assert
    zero steady-state recompiles exactly the way the CNN path does.
    """

    def __init__(self, seq_buckets: Sequence[int], *,
                 prefill_builder: Callable, decode_builder: Callable):
        bs = tuple(sorted({int(b) for b in seq_buckets}))
        if not bs or bs[0] < 1:
            raise ValueError(
                f"sequence-bucket ladder must be positive, got {seq_buckets}")
        self.buckets = bs
        self._prefill_builder = prefill_builder
        self._decode_builder = decode_builder
        self.stats = PlanCacheStats()
        self._prefill_plans: dict = {}
        self._decode_plan = None

    def __len__(self) -> int:
        return len(self._prefill_plans) + (self._decode_plan is not None)

    def bucket_for(self, s: int) -> int:
        """Smallest sequence bucket >= s.  Prompts longer than the top
        bucket are an error (no chunked prefill — the KV cache is sized
        by the compile-time ``max_len``, not grown on demand)."""
        if s < 1:
            raise ValueError(f"prompt length must be >= 1, got {s}")
        for b in self.buckets:
            if b >= s:
                return b
        raise ValueError(
            f"prompt length {s} exceeds the top sequence bucket "
            f"{self.buckets[-1]}; recompile with a longer bucket ladder")

    def prefill_plan(self, bucket: int):
        """Cached prefill plan for one sequence bucket (built on first
        use)."""
        plan = self._prefill_plans.get(int(bucket))
        if plan is not None:
            self.stats.hits += 1
            return plan
        plan = self._prefill_builder(int(bucket))
        self._prefill_plans[int(bucket)] = plan
        self.stats.compiles += 1
        return plan

    def decode_plan(self):
        """The one cached decode-step plan (built on first use)."""
        if self._decode_plan is None:
            self._decode_plan = self._decode_builder()
            self.stats.compiles += 1
        else:
            self.stats.hits += 1
        return self._decode_plan

    def record_execution(self, *, padded_rows: int = 0) -> None:
        """Count one plan call (and any pad rows/columns it carried)."""
        self.stats.executions += 1
        self.stats.padded_rows += int(padded_rows)


# ---------------------------------------------------------------------------
# Ping-pong buffer sizing / memory-access accounting.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class LayerMem:
    name: str
    in_shape: Tuple[int, ...]
    out_shape: Tuple[int, ...]
    act_bits: int                 # bits per activation element (T, packed)
    weight_bytes: int             # parameter bytes at weight_bits resolution
    act_reads: int                # activation elements read (with row reuse)
    act_writes: int
    weight_reads: int             # weight elements fetched (row reuse: once
                                  # per (out-row, time step) per kernel row)


@dataclasses.dataclass
class MemoryReport:
    layers: List[LayerMem]
    buf2d_bytes: int              # ping+pong 2-D activation buffers
    buf1d_bytes: int              # ping+pong 1-D activation buffers
    weight_bram_bytes: int        # on-chip weight storage if it fits
    needs_dram: bool              # paper: VGG-11 streams weights from DRAM
    total_param_bytes: int

    @property
    def total_buffer_bytes(self) -> int:
        return self.buf2d_bytes + self.buf1d_bytes


def memory_report(
    qnet: conversion.QuantizedNet,
    input_hw: Tuple[int, int, int],
    *,
    bram_capacity_bytes: int = 8 << 20,
) -> MemoryReport:
    """Static ping-pong sizing + access counts for one inference (batch 1).

    Mirrors Sec. III-C: two 2-D buffers sized to the largest conv/pool
    feature map (at T bits per element, packed), two 1-D buffers for the
    linear layers; weights on-chip iff they fit ``bram_capacity_bytes``.
    """
    T = qnet.num_steps
    h, w, c = input_hw
    shape: Tuple[int, ...] = (h, w, c)
    layer_mems: List[LayerMem] = []
    max2d = int(np.prod(shape))
    max1d = 0
    total_param_bytes = 0

    for (kind, cfg), qp in zip(qnet.static, qnet.qlayers):
        in_shape = shape
        if kind == "conv":
            kh, kw, cin, cout = qp["w_q"].shape
            stride = cfg.get("stride", 1)
            if cfg.get("padding", "VALID") == "SAME":
                ho = -(-shape[0] // stride)
                wo = -(-shape[1] // stride)
            else:
                ho = (shape[0] - kh) // stride + 1
                wo = (shape[1] - kw) // stride + 1
            shape = (ho, wo, cout)
            wbytes = math.ceil(kh * kw * cin * cout * qnet.weight_bits / 8)
            total_param_bytes += wbytes
            layer_mems.append(LayerMem(
                name=f"conv{kh}x{kw}x{cin}->{cout}",
                in_shape=in_shape, out_shape=shape, act_bits=T,
                weight_bytes=wbytes,
                # row-based reuse: each input row read once per (out-channel
                # pass, time step); kernel rows re-fetched per output row.
                act_reads=T * cin * shape[0] * in_shape[1] * kh // 1,
                act_writes=int(np.prod(shape)),
                weight_reads=T * cin * cout * kh * kw * shape[0],
            ))
            max2d = max(max2d, int(np.prod(shape)))
        elif kind == "linear":
            fin, fout = qp["w_q"].shape
            shape = (fout,)
            wbytes = math.ceil(fin * fout * qnet.weight_bits / 8)
            total_param_bytes += wbytes
            layer_mems.append(LayerMem(
                name=f"linear{fin}->{fout}",
                in_shape=in_shape, out_shape=shape, act_bits=T,
                weight_bytes=wbytes,
                act_reads=T * fin, act_writes=fout,
                weight_reads=T * fin * fout,
            ))
            max1d = max(max1d, fin, fout)
        elif kind == "pool":
            win = cfg["window"]
            shape = (shape[0] // win, shape[1] // win, shape[2])
            layer_mems.append(LayerMem(
                name=f"pool{win}", in_shape=in_shape, out_shape=shape,
                act_bits=T, weight_bytes=0,
                act_reads=T * int(np.prod(in_shape)),
                act_writes=int(np.prod(shape)), weight_reads=0,
            ))
            max2d = max(max2d, int(np.prod(shape)))
        elif kind == "flatten":
            shape = (int(np.prod(shape)),)
            max1d = max(max1d, shape[0])

    buf2d = 2 * math.ceil(max2d * T / 8)          # ping + pong, T-bit packed
    buf1d = 2 * math.ceil(max1d * T / 8)
    needs_dram = total_param_bytes > bram_capacity_bytes
    return MemoryReport(
        layers=layer_mems,
        buf2d_bytes=buf2d,
        buf1d_bytes=buf1d,
        weight_bram_bytes=0 if needs_dram else total_param_bytes,
        needs_dram=needs_dram,
        total_param_bytes=total_param_bytes,
    )
