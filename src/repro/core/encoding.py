"""Neural encodings — the paper's central primitive, as first-class specs.

Every encoding here is a *plane-weight scheme*: a spike train of length
``T`` decodes to ``q = sum_t w_t * s_t`` for a per-time-step weight
schedule ``w_t`` (normalized by the number of repeated periods, if any).
The four shipped schemes (see ``docs/encodings.md`` for the user guide):

* **radix** — ``w_t = 2^(T-1-t)`` (earlier spikes more significant); the
  train *is* the T-bit binary expansion of an integer in ``[0, 2^T - 1]``.
* **rate**  — ``w_t = 1``; the spike *count* is the activation (``T + 1``
  levels — the paper's motivating asymmetry versus radix).
* **TTFS**  — ``w_t = 2^(T-1-t)`` with at most ONE spike per activation,
  at ``t = T - 1 - msb(q)``: earlier spike = larger (power-of-two) value.
* **phase** — radix weights tiled over ``P`` repeated periods of
  ``K = T / P`` phases, ``w_t = 2^(K-1-(t mod K))``, decode divides by
  ``P`` (the classic per-phase weighted-spike schedule, period-averaged).

This module provides the encode/decode pairs, bit-plane packing (the packed
representation along the time axis is exactly the integer ``q``), and the
:class:`EncodingSpec` hierarchy `repro.api` dispatches on.

Conventions
-----------
* Spike planes are laid out time-major: ``planes[t]`` is the t-th time step,
  with ``t = 0`` the most-significant bit (MSB-first, matching the paper's
  left-shift accumulation order, Alg. 1 line 12).
* Planes are ``int8`` in {0, 1}; packed activations are ``uint8`` for
  ``T <= 8`` (the paper uses T in [3, 6]) and ``int32`` above that.
* Real-valued activations are mapped to integers with a per-tensor (or
  per-channel) positive scale:  ``q = clip(floor(x / scale * (2^T - 1)), 0,
  2^T - 1)``.  ReLU is implied by the lower clip — exactly the paper's
  "apply ReLU and requantize".
"""

from __future__ import annotations

import dataclasses
import functools
from typing import ClassVar, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "max_level",
    "quantize",
    "dequantize",
    "encode",
    "decode",
    "pack_planes",
    "unpack_planes",
    "pow2_floor",
    "rate_encode",
    "rate_decode",
    "radix_weights",
    "KernelSchedule",
    "KERNEL_OUT_GRIDS",
    "EncodingSpec",
    "RadixEncoding",
    "RateEncoding",
    "TTFSEncoding",
    "PhaseEncoding",
    "SPECS",
    "support_matrix",
    "support_matrix_markdown",
]


def max_level(num_steps: int) -> int:
    """Largest integer representable by a radix spike train of length T."""
    return (1 << num_steps) - 1


def _packed_dtype(num_steps: int):
    return jnp.uint8 if num_steps <= 8 else jnp.int32


def _np_radix_weights(num_steps: int) -> np.ndarray:
    """numpy twin of :func:`radix_weights` — safe to call inside jit traces
    (``EncodingSpec.plane_weights`` contracts to return host constants)."""
    return 1 << np.arange(num_steps - 1, -1, -1)


def radix_weights(num_steps: int, dtype=jnp.int32) -> jax.Array:
    """Per-time-step weights ``2^(T-1-t)``, MSB first: [2^(T-1), ..., 2, 1]."""
    return jnp.asarray(_np_radix_weights(num_steps), dtype=dtype)


def quantize(x: jax.Array, num_steps: int, scale: jax.Array | float = 1.0) -> jax.Array:
    """Real activation -> integer level in [0, 2^T - 1] (ReLU + requantize).

    ``scale`` is the real value mapped to full-scale; it may be a scalar or
    broadcastable per-channel array.  Uses floor rounding (the hardware
    truncates — spikes that "didn't happen" carry no value).
    """
    lvl = max_level(num_steps)
    q = jnp.floor(x / scale * (lvl + 1))
    return jnp.clip(q, 0, lvl).astype(_packed_dtype(num_steps))


def dequantize(q: jax.Array, num_steps: int, scale: jax.Array | float = 1.0) -> jax.Array:
    """Integer level -> real activation (midpoint-free truncation inverse)."""
    lvl = max_level(num_steps)
    return q.astype(jnp.float32) * (jnp.asarray(scale, jnp.float32) / (lvl + 1))


def encode(q: jax.Array, num_steps: int) -> jax.Array:
    """Integer levels -> radix spike train, shape ``(T,) + q.shape``.

    ``planes[t] = (q >> (T-1-t)) & 1`` — MSB first.  Output int8 in {0,1}.
    """
    q = q.astype(jnp.int32)
    shifts = jnp.arange(num_steps - 1, -1, -1, dtype=jnp.int32)
    shifts = shifts.reshape((num_steps,) + (1,) * q.ndim)
    planes = (q[None, ...] >> shifts) & 1
    return planes.astype(jnp.int8)


def decode(planes: jax.Array) -> jax.Array:
    """Radix spike train ``(T, ...)`` -> integer levels (int32).

    Implemented as the paper's Horner accumulation: acc = (acc << 1) + s_t.
    """
    num_steps = planes.shape[0]

    def body(acc, plane):
        return (acc << 1) + plane.astype(jnp.int32), None

    acc0 = jnp.zeros(planes.shape[1:], jnp.int32)
    acc, _ = jax.lax.scan(body, acc0, planes.astype(jnp.int32))
    del num_steps
    return acc


def pack_planes(planes: jax.Array) -> jax.Array:
    """Pack a (T, ...) spike train along time into the integer activation.

    For radix encoding this is *identical* to :func:`decode`; it exists as a
    named op because the packed form is the memory format the TPU kernels
    consume (1 byte per activation instead of T bytes / T floats).
    """
    num_steps = planes.shape[0]
    return decode(planes).astype(_packed_dtype(num_steps))


def unpack_planes(q: jax.Array, num_steps: int) -> jax.Array:
    """Inverse of :func:`pack_planes` (== :func:`encode`)."""
    return encode(q, num_steps)


def pow2_floor(q: jax.Array, num_steps: int) -> jax.Array:
    """Largest power of two ``<= q`` (0 for 0) — the TTFS level grid.

    Args:
        q: non-negative integer levels, any shape, values ``< 2^num_steps``.
        num_steps: bit width bounding the values of ``q``.

    Returns:
        int32 array of the same shape with every element projected onto
        ``{0} | {2^k : k < num_steps}`` (``2^msb(q)``; 0 stays 0).

    >>> import jax.numpy as jnp
    >>> pow2_floor(jnp.asarray([0, 1, 2, 3, 9, 15]), 4).tolist()
    [0, 1, 2, 2, 8, 8]
    """
    q = q.astype(jnp.int32)
    out = jnp.zeros_like(q)
    for s in range(num_steps):
        out = jnp.where(q >= (1 << s), jnp.int32(1 << s), out)
    return out


# ---------------------------------------------------------------------------
# Rate-coding baseline (what traditional SNN accelerators consume).
# ---------------------------------------------------------------------------


def rate_encode(
    x: jax.Array,
    num_steps: int,
    scale: jax.Array | float = 1.0,
    key: Optional[jax.Array] = None,
) -> jax.Array:
    """Rate coding: spike probability proportional to magnitude.

    Deterministic variant (key=None) emits evenly spaced spikes via error
    accumulation (a.k.a. sigma-delta); stochastic variant draws Bernoulli
    spikes.  Returns (T, ...) int8.  Needs ``num_steps`` ~ 2^T steps to match
    the precision radix coding achieves with T steps — the paper's motivating
    asymmetry, which benchmarks/table1 quantifies.
    """
    p = jnp.clip(x / scale, 0.0, 1.0)
    if key is not None:
        u = jax.random.uniform(key, (num_steps,) + p.shape)
        return (u < p[None]).astype(jnp.int8)

    def body(err, _):
        err = err + p
        spike = (err >= 1.0).astype(jnp.int8)
        return err - spike, spike

    _, spikes = jax.lax.scan(body, jnp.zeros_like(p), None, length=num_steps)
    return spikes


def rate_decode(planes: jax.Array, scale: jax.Array | float = 1.0) -> jax.Array:
    """Spike-count decode for rate-coded trains."""
    num_steps = planes.shape[0]
    return planes.astype(jnp.float32).sum(0) * (jnp.asarray(scale, jnp.float32) / num_steps)


# ---------------------------------------------------------------------------
# Encoding specs — the first-class, swappable encoding component.
# ---------------------------------------------------------------------------


KERNEL_OUT_GRIDS: Tuple[str, ...] = ("dense", "pow2")
"""Level grids the kernel epilogue can project requantized outputs onto."""


@dataclasses.dataclass(frozen=True)
class KernelSchedule:
    """How an encoding's plane-weight algebra maps onto the radix kernels.

    This is the *declaration* that makes a spec kernels-capable
    (``EncodingSpec.kernel_schedule()``): the kernels never see the spec
    itself, only this schedule, so new codes plug into the kernels path
    without touching kernel source (docs/kernels.md walks the mapping).

    Attributes:
        packed_bits: bit-serial extraction width — bits of one period's
            packed level the kernels unroll over (phase: ``K = T / P``).
        periods: plane-schedule replay count for the bitserial dataflow
            (phase: ``P``; the in-kernel accumulator divides back down,
            exactly).  The fused dataflow never replays — the radix
            identity collapses one period into the packed level.
        out_level: the fused epilogue's clip ceiling (the spec's
            ``max_level``); defaults to ``2^packed_bits - 1``.
        out_grid: the epilogue's output level grid.  ``"dense"`` clips
            to ``[0, out_level]``; ``"pow2"`` additionally floors onto
            ``{0} | {2^k}`` (:func:`pow2_floor`) — TTFS's in-kernel
            log-spaced decode, re-timing the single output spike.
    """

    packed_bits: int
    periods: int = 1
    out_level: Optional[int] = None
    out_grid: str = "dense"

    def __post_init__(self):
        if self.out_level is None:
            object.__setattr__(self, "out_level",
                               (1 << self.packed_bits) - 1)


@dataclasses.dataclass(frozen=True)
class EncodingSpec:
    """A neural encoding as a first-class object (the `repro.api` contract).

    The paper's accelerator claims to support *emerging neural encodings*
    generically; an ``EncodingSpec`` is how the software twin states one.
    A spec owns the full numeric semantics of an encoding —

    * ``quantize``/``dequantize``: real activation <-> integer level,
    * ``encode``/``decode``:       integer level <-> (T, ...) spike planes,
    * ``reduce_planes``:           per-time-step layer accumulators -> one
                                   int32 membrane (the output-logic sum),
    * ``requantize``:              membrane -> next layer's integer levels,

    and *declares* what it can run on: which execution backends
    (``backends``), which in-kernel dataflows (``kernel_dataflows``), and
    which pooling-unit modes (``pool_modes``) preserve its semantics.
    ``core/conversion.convert`` folds scales using ``levels``;
    ``core/engine`` and ``repro.api`` dispatch on the declarations instead
    of bare ``method=`` strings.

    The unifying algebra (DESIGN.md §7) is the **plane-weight schedule**
    ``plane_weights()``: every shipped encoding decodes a train as
    ``sum_t w_t * s_t`` (divided by ``periods`` for period-repeated codes),
    so ``decode`` and ``reduce_planes`` have generic weighted-sum
    implementations here and subclasses only state the schedule.

    Specs are frozen (hashable) so they can serve as cache-key components
    and jit-static metadata.  Subclass to add a new encoding (e.g. a
    differential/temporal scheme) without touching the engine.
    """

    num_steps: int

    name: ClassVar[str] = "abstract"
    backends: ClassVar[Tuple[str, ...]] = ()
    kernel_dataflows: ClassVar[Tuple[str, ...]] = ()
    pool_modes: ClassVar[Tuple[str, ...]] = ()
    # residual blocks (layer kind "block", DESIGN.md §2): tested, and so
    # accepted, for the radix code only
    residual_blocks: ClassVar[bool] = False
    levels_doc: ClassVar[str] = "?"    # human formula for docs/encodings.md
    periods: ClassVar[int] = 1         # repeated-period count (phase: P)

    def __post_init__(self):
        if self.num_steps < 1:
            raise ValueError(
                f"num_steps must be >= 1, got {self.num_steps}")

    # -- capacity ----------------------------------------------------------

    @property
    def levels(self) -> int:
        """Distinct integer levels a train of ``num_steps`` represents."""
        raise NotImplementedError

    @property
    def max_level(self) -> int:
        """Largest integer level (``levels - 1``)."""
        return self.levels - 1

    @property
    def packed_bits(self) -> int:
        """Bits of the packed integer form consumed by the kernels path.

        Equals ``num_steps`` except for period-repeated codes (phase:
        ``num_steps / periods`` — one period's worth of bits); the fused
        epilogue clamps its packed output to ``2^packed_bits - 1``.
        """
        return self.num_steps

    @property
    def packed_dtype(self):
        """dtype of packed levels (uint8 while ``max_level`` fits a byte)."""
        return jnp.uint8 if self.max_level <= 255 else jnp.int32

    @property
    def radix_planes(self) -> bool:
        """True when ``encode`` emits the MSB-first binary expansion of the
        packed level (radix, TTFS, single-period phase) — which is what
        permits bit-plane-domain ops like the lexicographic spiking
        max-pool (``layers.snn_max_pool``) without a decode round trip."""
        return False

    def plane_weights(self) -> np.ndarray:
        """Per-time-step decode weights ``w_t``, shape ``(num_steps,)``.

        The train's value is ``sum_t w_t * s_t`` (``// periods`` for
        period-repeated codes) — the generalized twin-pair algebra every
        generic ``decode``/``reduce_planes`` implementation runs on.
        """
        raise NotImplementedError

    def representable_levels(self) -> np.ndarray:
        """All integer levels ``encode`` can represent exactly (the image
        of ``quantize``/``requantize``) — the decode round-trip domain.
        Dense ``[0, max_level]`` except for sparse grids (TTFS)."""
        return np.arange(self.levels)

    @property
    def scale_factor(self) -> float:
        """Full-scale headroom multiplier folded into every calibrated
        activation scale at conversion time (``convert`` multiplies its
        calibration scales by this, so the quantize/bias/multiplier/logit
        algebra stays consistent).  1.0 for most encodings."""
        return 1.0

    # -- numeric semantics (generic over the level grid / plane weights;
    #    encode is the one subclass-specific piece) -------------------------

    def quantize(self, x: jax.Array, scale: jax.Array | float = 1.0) -> jax.Array:
        """Real activation -> integer level (ReLU + requantize).

        Args:
            x: real activations, any shape.
            scale: real value mapped to full scale (scalar or per-channel
                broadcastable array; must be positive).

        Returns:
            ``clip(floor(x / scale * levels), 0, max_level)`` in
            ``packed_dtype`` — floor rounding, truncating like hardware.
        """
        q = jnp.floor(x / jnp.asarray(scale, jnp.float32) * self.levels)
        return jnp.clip(q, 0, self.max_level).astype(self.packed_dtype)

    def dequantize(self, q: jax.Array, scale: jax.Array | float = 1.0) -> jax.Array:
        """Integer level -> real activation (``q * scale / levels``)."""
        return q.astype(jnp.float32) * (
            jnp.asarray(scale, jnp.float32) / self.levels)

    def encode(self, q: jax.Array) -> jax.Array:
        """Integer levels -> spike planes, shape ``(num_steps,) + q.shape``.

        Subclass responsibility (the one scheme-specific op).  Must satisfy
        ``decode(encode(q)) == q`` for every ``q`` in
        :meth:`representable_levels`.  Returns int8 planes in {0, 1}.
        """
        raise NotImplementedError

    def decode(self, planes: jax.Array) -> jax.Array:
        """Spike planes ``(num_steps, ...)`` -> integer levels (int32).

        Generic weighted-plane sum ``sum_t w_t * planes[t]`` (divided —
        exactly — by ``periods`` for period-repeated codes).
        """
        return self.reduce_planes(planes)

    def reduce_planes(self, per_step: jax.Array) -> jax.Array:
        """Per-time-step layer accumulators -> one int32 membrane.

        The output-logic sum: ``sum_t w_t * per_step[t] // periods``.  By
        linearity this equals the layer applied to the packed level, which
        is the bit-exact twin-pair contract (DESIGN.md §1/§7).  Applied to
        raw planes it *is* :meth:`decode`.
        """
        w = jnp.asarray(self.plane_weights(), jnp.int32)
        w = w.reshape((self.num_steps,) + (1,) * (per_step.ndim - 1))
        acc = (per_step.astype(jnp.int32) * w).sum(0)
        if self.periods > 1:
            acc = acc // self.periods    # exact: acc is periods * value
        return acc

    def requantize(self, acc: jax.Array, mult) -> jax.Array:
        """ReLU + requantize a layer accumulator to this encoding's levels.

        Args:
            acc: int32 layer accumulator (bias already added).
            mult: folded requantization multiplier (scalar or per-channel
                row, float32) produced by ``conversion.convert``.

        Returns:
            ``clip(floor(acc * mult), 0, max_level)`` in ``packed_dtype`` —
            the semantic contract of the kernels' fused output-logic
            epilogue, truncating like hardware.
        """
        q = jnp.floor(acc.astype(jnp.float32) * mult)
        return jnp.clip(q, 0, self.max_level).astype(self.packed_dtype)

    # -- capability checks (used by repro.api / core.engine) ---------------

    def supports_pool(self, pool_mode: str) -> bool:
        """True iff ``pool_mode`` is in this spec's declared ``pool_modes``."""
        return pool_mode in self.pool_modes

    def validate_static(self, static) -> None:
        """Check every pool in a network description against this
        encoding's declared ``pool_modes`` (shared by convert /
        Accelerator.compile / the engine's runtime guard).

        Args:
            static: the conversion-format layer description (tuple of
                ``(kind, cfg)`` pairs).

        Raises:
            ValueError: a pool layer uses a mode this encoding does not
                preserve, naming the supported modes; or a residual
                ``"block"`` in a net whose encoding does not run them.
        """
        for kind, cfg in static:
            if kind == "block" and not self.residual_blocks:
                raise ValueError(
                    f"{self.name} encoding does not run layer kind "
                    f"'block' (residual blocks run in the radix code)")
            if kind == "pool" and not self.supports_pool(
                    cfg.get("mode", "or")):
                raise ValueError(
                    f"{self.name} encoding does not preserve pool mode "
                    f"{cfg.get('mode', 'or')!r} (supported: "
                    f"{self.pool_modes})")

    def kernel_schedule(self) -> KernelSchedule:
        """This encoding's :class:`KernelSchedule` — the declaration the
        kernels path executes instead of the spec itself.

        The base implementation states the generic dense-grid schedule
        (``packed_bits``/``periods``, clip to ``max_level``); subclasses
        with non-dense requantize grids override it (TTFS projects onto
        the pow2 grid).  This is what replaced the old hard-wired
        ``levels == 2^T`` kernels restriction: a new code plugs into the
        kernels by declaring its schedule, not by editing kernel source.

        Raises:
            ValueError: the encoding declares no kernel dataflow.
        """
        if not self.kernel_dataflows:
            raise ValueError(
                f"{self.name} encoding has no kernel dataflow; supported "
                f"backends: {self.backends}")
        return KernelSchedule(packed_bits=self.packed_bits,
                              periods=self.periods,
                              out_level=self.max_level)

    def validate_dataflow(self, dataflow: Optional[str]) -> str:
        """Resolve/validate an in-kernel dataflow for the kernels backend.

        Args:
            dataflow: requested dataflow, or None for this encoding's
                default (``kernel_dataflows[0]``).

        Returns:
            The resolved dataflow name.

        Raises:
            ValueError: the encoding declares no kernel dataflow, its
                :meth:`kernel_schedule` is inconsistent with its own
                level algebra, or ``dataflow`` is not among its declared
                ``kernel_dataflows``.
        """
        sched = self.kernel_schedule()   # raises for jnp-only specs
        # the schedule must be able to carry the spec's own levels: the
        # bit-serial extraction covers packed_bits bits and the packed
        # activations ride uint8 buffers, so the epilogue's clip ceiling
        # (== the spec's max_level) must fit both.
        if sched.out_grid not in KERNEL_OUT_GRIDS:
            raise ValueError(
                f"{self.name} encoding declares kernel out_grid "
                f"{sched.out_grid!r}; supported: {KERNEL_OUT_GRIDS}")
        if (sched.out_level != self.max_level
                or sched.out_level > (1 << sched.packed_bits) - 1
                or sched.out_level > 255):
            raise ValueError(
                f"{self.name} encoding declares kernel dataflows but its "
                f"schedule is inconsistent: out_level={sched.out_level} "
                f"must equal max_level={self.max_level}, fit "
                f"packed_bits={sched.packed_bits} bits "
                f"(<= {(1 << sched.packed_bits) - 1}) and fit the packed "
                f"uint8 buffers (<= 255)")
        if dataflow is None:
            return self.kernel_dataflows[0]
        if dataflow not in self.kernel_dataflows:
            raise ValueError(
                f"dataflow must be one of {self.kernel_dataflows} for "
                f"{self.name} encoding, got {dataflow!r}")
        return dataflow


@dataclasses.dataclass(frozen=True)
class RadixEncoding(EncodingSpec):
    """The paper's radix encoding: ``planes[t]`` weighs ``2^(T-1-t)``.

    T steps carry ``2^T`` levels; the packed time axis IS the integer
    activation, which is what admits the single-pass kernels backend
    (both the TPU-native "fused" dataflow and the paper-faithful
    "bitserial" one).
    """

    name: ClassVar[str] = "radix"
    backends: ClassVar[Tuple[str, ...]] = ("kernels", "jnp")
    kernel_dataflows: ClassVar[Tuple[str, ...]] = ("fused", "bitserial")
    pool_modes: ClassVar[Tuple[str, ...]] = ("or", "avg", "max")
    residual_blocks: ClassVar[bool] = True
    levels_doc: ClassVar[str] = "2^T"

    @property
    def levels(self) -> int:
        return 1 << self.num_steps

    @property
    def radix_planes(self) -> bool:
        return True

    def plane_weights(self) -> np.ndarray:
        """``[2^(T-1), ..., 2, 1]`` — MSB first."""
        return _np_radix_weights(self.num_steps)

    def quantize(self, x, scale=1.0):
        return quantize(x, self.num_steps, scale)

    def dequantize(self, q, scale=1.0):
        return dequantize(q, self.num_steps, scale)

    def encode(self, q):
        return encode(q, self.num_steps)

    def decode(self, planes):
        return decode(planes)

    def reduce_planes(self, per_step):
        """Horner accumulation (acc << 1) + I_t over the time axis —
        identical to ``neuron.radix_membrane`` (the "<<" block, Fig. 2);
        equal to the generic weighted-plane sum by the radix identity."""

        def body(acc, cur):
            return (acc << 1) + cur, None

        acc0 = jnp.zeros(per_step.shape[1:], jnp.int32)
        acc, _ = jax.lax.scan(body, acc0, per_step.astype(jnp.int32))
        return acc


@dataclasses.dataclass(frozen=True)
class RateEncoding(EncodingSpec):
    """Rate coding: the spike *count* over T steps is the activation.

    T steps carry only ``T + 1`` levels — the paper's motivating asymmetry
    versus radix (2^T levels).  All time steps weigh 1, so spike planes
    reduce by a plain sum and the quantized-ANN twin runs levels in
    [0, T] through the same integer layers; only linear (sum) pooling
    commutes with the per-plane path, hence ``pool_modes = ("avg",)``.
    The deterministic encoder is an exact integer sigma-delta: an integer
    level q emits exactly q evenly spaced spikes.

    ``scale`` is an extra full-scale headroom factor: :func:`convert`
    folds it into every calibrated activation scale (via
    :attr:`scale_factor`), keeping the bias/multiplier/logit algebra
    consistent with the coarser quantization grid (1.0 = use calibration
    as-is).
    """

    scale: float = 1.0

    name: ClassVar[str] = "rate"
    backends: ClassVar[Tuple[str, ...]] = ("jnp",)
    kernel_dataflows: ClassVar[Tuple[str, ...]] = ()
    pool_modes: ClassVar[Tuple[str, ...]] = ("avg",)
    levels_doc: ClassVar[str] = "T + 1"

    def __post_init__(self):
        super().__post_init__()
        if self.scale <= 0:
            raise ValueError(f"scale must be positive, got {self.scale}")

    @property
    def levels(self) -> int:
        return self.num_steps + 1

    @property
    def scale_factor(self) -> float:
        return self.scale

    def plane_weights(self) -> np.ndarray:
        """All ones — every time step weighs the same (count coding)."""
        return np.ones(self.num_steps, np.int64)

    def encode(self, q):
        """Integer sigma-delta: exactly q spikes, evenly spaced, per
        element — integer error accumulation so the round trip is exact."""
        q = q.astype(jnp.int32)
        T = self.num_steps

        def body(err, _):
            err = err + q
            spike = (err >= T).astype(jnp.int8)
            return err - spike.astype(jnp.int32) * T, spike

        _, planes = jax.lax.scan(body, jnp.zeros_like(q), None, length=T)
        return planes

    def decode(self, planes):
        return planes.astype(jnp.int32).sum(0)

    def reduce_planes(self, per_step):
        return per_step.astype(jnp.int32).sum(0)


@dataclasses.dataclass(frozen=True)
class TTFSEncoding(EncodingSpec):
    """Time-to-first-spike coding: ONE spike, whose *timing* is the value.

    A quantized activation ``q`` emits a single spike at
    ``t = T - 1 - msb(q)`` (larger value -> earlier spike; ``q = 0`` emits
    nothing).  With the radix plane weights ``2^(T-1-t)`` the weighted-plane
    reduce recovers ``2^msb(q)`` — an argmax-style decode over the one-hot
    train — so the representable grid is **logarithmic**:
    ``{0, 1, 2, 4, ..., 2^(T-1)}``, ``T + 1`` values from a ``2^T``-unit
    full scale.  ``quantize``/``requantize`` project onto that grid
    (:func:`pow2_floor`), keeping the packed and spike-plane paths
    bit-exact twins.

    The payoff is extreme sparsity — at most one spike per activation per
    layer versus up to ``T`` for radix — at the cost of log-spaced
    precision (docs/encodings.md quantifies the trade).  The packed level
    is a power of two whose binary expansion IS the one-hot train, so the
    KERNELS backend runs TTFS end-to-end: the ``bitserial`` dataflow
    replays the radix plane schedule over trains where at most one plane
    per activation carries a spike (the plane-occupancy prepass skips
    planes no activation uses — DESIGN.md §8), the ``fused`` dataflow
    collapses the train into one packed MXU pass, and the epilogue's
    ``"pow2"`` output grid (:meth:`kernel_schedule`) re-times the single
    output spike in-kernel, bit-exact with :meth:`requantize`.  ``"or"``
    pooling is excluded because OR-ing one-hot trains yields multi-spike
    trains (not TTFS codewords); ``max`` (lexicographic, stays one-hot)
    and ``avg`` (linear sum, requantized by the next layer) are
    preserved.
    """

    name: ClassVar[str] = "ttfs"
    backends: ClassVar[Tuple[str, ...]] = ("kernels", "jnp")
    kernel_dataflows: ClassVar[Tuple[str, ...]] = ("fused", "bitserial")
    pool_modes: ClassVar[Tuple[str, ...]] = ("avg", "max")
    levels_doc: ClassVar[str] = "T + 1 (log-spaced)"

    @property
    def levels(self) -> int:
        """Grid units of full scale (2^T); only ``num_steps + 1`` of them
        — 0 and the powers of two — are representable (one per spike
        time, plus the empty train)."""
        return 1 << self.num_steps

    @property
    def radix_planes(self) -> bool:
        """One-hot trains at the MSB are exactly the binary expansion of
        a power-of-two level, so bit-plane-domain ops stay valid."""
        return True

    def plane_weights(self) -> np.ndarray:
        """Radix weights — a spike at ``t`` decodes to ``2^(T-1-t)``."""
        return _np_radix_weights(self.num_steps)

    def representable_levels(self) -> np.ndarray:
        return np.concatenate(
            ([0], 1 << np.arange(self.num_steps, dtype=np.int64)))

    def kernel_schedule(self) -> KernelSchedule:
        """Radix extraction over the one-hot planes; the epilogue floors
        the requantized level onto the pow2 grid (``out_grid="pow2"``) —
        the output logic re-times exactly one spike, in-kernel."""
        return dataclasses.replace(super().kernel_schedule(),
                                   out_grid="pow2")

    def quantize(self, x, scale=1.0):
        """Radix quantize, then floor onto the power-of-two grid.

        >>> import jax.numpy as jnp
        >>> TTFSEncoding(4).quantize(jnp.asarray([0.3, 0.6375])).tolist()
        [4, 8]
        """
        q = quantize(x, self.num_steps, scale)
        return pow2_floor(q, self.num_steps).astype(self.packed_dtype)

    def encode(self, q):
        """One-hot planes: a single spike at ``t = T - 1 - msb(q)``.

        Defined for any level in ``[0, 2^T - 1]`` (non-grid levels spike
        at their MSB, i.e. encode as ``pow2_floor(q)``); exact on the
        representable grid.
        """
        q = q.astype(jnp.int32)
        shifts = jnp.arange(self.num_steps - 1, -1, -1, dtype=jnp.int32)
        shifts = shifts.reshape((self.num_steps,) + (1,) * q.ndim)
        planes = (q[None, ...] >> shifts) == 1    # true only at the MSB
        return planes.astype(jnp.int8)

    def requantize(self, acc, mult):
        """Base requantize, then floor onto the power-of-two grid (the
        output logic of a TTFS layer re-times exactly one spike)."""
        q = jnp.floor(acc.astype(jnp.float32) * mult)
        q = jnp.clip(q, 0, self.max_level).astype(jnp.int32)
        return pow2_floor(q, self.num_steps).astype(self.packed_dtype)


@dataclasses.dataclass(frozen=True)
class PhaseEncoding(EncodingSpec):
    """Phase coding: radix plane weights tiled over repeated periods.

    ``num_steps = T`` total time steps split into ``periods = P`` repeats
    of ``K = T / P`` *phases*; a spike in phase ``p`` carries weight
    ``2^(K-1-p)`` regardless of which period it lands in (the classic
    per-phase weighted-spike schedule), so a train decodes as

        q = sum_t 2^(K-1-(t mod K)) * s_t / P,      q in [0, 2^K - 1].

    ``P = 1`` *is* radix coding; ``P > 1`` trades time steps for the
    period redundancy real phase-coded SNNs use against spike loss.  The
    packed integer form is one period's ``K`` bits, so phase runs on the
    **kernels** backend: the fused dataflow consumes the packed level in a
    single MXU pass, while the paper-faithful bitserial dataflow replays
    all ``P * K`` plane passes with the tiled weight schedule and divides
    the accumulator by ``P`` in-kernel (exactly — it is ``P ×`` an
    integer), which is where the ``P ×`` latency cost of period
    redundancy shows up (benchmarks/kernel_bench.py measures it).

    Args:
        num_steps: total time steps ``T`` (all periods).
        periods: repeat count ``P``; must divide ``num_steps``.

    Raises:
        ValueError: ``periods < 1`` or ``num_steps % periods != 0``.
    """

    periods: int = 1

    name: ClassVar[str] = "phase"
    backends: ClassVar[Tuple[str, ...]] = ("kernels", "jnp")
    kernel_dataflows: ClassVar[Tuple[str, ...]] = ("fused", "bitserial")
    pool_modes: ClassVar[Tuple[str, ...]] = ("or", "avg", "max")
    levels_doc: ClassVar[str] = "2^(T/P)"

    def __post_init__(self):
        super().__post_init__()
        if self.periods < 1:
            raise ValueError(f"periods must be >= 1, got {self.periods}")
        if self.num_steps % self.periods:
            raise ValueError(
                f"num_steps={self.num_steps} must be divisible by "
                f"periods={self.periods} (each period spans "
                f"num_steps/periods phases)")

    @property
    def phases(self) -> int:
        """Phases per period (``K = num_steps / periods``)."""
        return self.num_steps // self.periods

    @property
    def packed_bits(self) -> int:
        return self.phases

    @property
    def levels(self) -> int:
        return 1 << self.phases

    @property
    def radix_planes(self) -> bool:
        """Single-period trains are plain radix planes; repeated periods
        are not a binary expansion of the packed level."""
        return self.periods == 1

    def plane_weights(self) -> np.ndarray:
        """``[2^(K-1), ..., 1]`` tiled ``P`` times (decode divides by P).

        >>> PhaseEncoding(4, periods=2).plane_weights().tolist()
        [2, 1, 2, 1]
        """
        return np.tile(_np_radix_weights(self.phases), self.periods)

    def encode(self, q):
        """One period's MSB-first bit planes, tiled ``periods`` times."""
        planes = encode(q, self.phases)
        return jnp.tile(planes, (self.periods,) + (1,) * (planes.ndim - 1))


# ---------------------------------------------------------------------------
# Spec registry + the generated capability matrix (docs/encodings.md).
# ---------------------------------------------------------------------------


SPECS: Tuple[type, ...] = (RadixEncoding, RateEncoding, TTFSEncoding,
                           PhaseEncoding)
"""Every shipped :class:`EncodingSpec` subclass, in documentation order."""


def support_matrix() -> list:
    """The shipped specs' declared capabilities, straight from the classes.

    Returns:
        One dict per spec: ``name``, ``levels`` (human formula),
        ``backends``, ``kernel_dataflows``, ``pool_modes``.  This is the
        single source of truth the docs table is generated from
        (tests/test_docs.py asserts ``docs/encodings.md`` matches).
    """
    return [dict(name=cls.name, levels=cls.levels_doc,
                 backends=cls.backends,
                 kernel_dataflows=cls.kernel_dataflows,
                 pool_modes=cls.pool_modes) for cls in SPECS]


def support_matrix_markdown() -> str:
    """Render :func:`support_matrix` as the markdown table embedded in
    ``docs/encodings.md`` between the ``support-matrix`` markers."""
    fmt = "| {:<8} | {:<18} | {:<13} | {:<17} | {:<12} |".format
    lines = [fmt("encoding", "levels (T steps)", "backends",
                 "kernel dataflows", "pool modes"),
             "|" + "|".join("-" * n for n in (10, 20, 15, 19, 14)) + "|"]
    for row in support_matrix():
        join = lambda t: ", ".join(t) if t else "—"
        lines.append(fmt(row["name"], row["levels"], join(row["backends"]),
                         join(row["kernel_dataflows"]),
                         join(row["pool_modes"])))
    return "\n".join(lines)
