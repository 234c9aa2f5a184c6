"""jit'd public wrappers around the Pallas kernels.

Handles:
* backend dispatch — compiled Pallas on TPU, ``interpret=True`` on CPU
  (the kernel body runs in Python for bit-exact validation),
* padding to block multiples (kernels require aligned shapes),
* layout conveniences (SAME padding, strides, bias) the raw kernels omit,
* the fused output-logic epilogue: passing ``mult`` makes conv/matmul emit
  packed uint8 levels directly (bias + requantize + clamp fused in-kernel,
  DESIGN.md §2) instead of raw int32 accumulators.

The ``method`` flag selects the paper-faithful bit-serial dataflow
("bitserial") or the TPU-native fused int8 pass ("fused") — both bit-exact
against kernels/ref.py oracles (tests/test_kernels.py and
tests/test_fused_epilogue.py sweep shapes, T, strides, methods).
``sparsity=True`` adds the plane-occupancy prepass (DESIGN.md §8,
docs/kernels.md): one bitwise-OR reduction finds bit planes no activation
spikes on, and the kernels skip (bitserial) or mask (fused) them —
bit-exact, and where TTFS's one-spike trains pay off.

Autotuning (docs/kernels.md §7): ``autotune=True`` resolves an execution
strategy (:class:`~repro.kernels.autotune.KernelConfig` — Pallas tile
shapes + MXU dot lowering + plane-parallel grid, or the jitted XLA twin
of the same plane-pass math) by timing the legal candidates on the actual
inputs and caching the winner per ``(shape, schedule, dataflow, backend)``
in the process + on-disk table.  ``config=`` pins an explicit strategy.
Every strategy is bit-exact — non-default dot lowerings are only ever
candidates when ``autotune.exact_lowering`` proves them so.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.encoding import EncodingSpec, KernelSchedule
from repro.kernels import autotune as autotune_mod
from repro.kernels import radix_attn
from repro.kernels.autotune import KernelConfig
from repro.kernels.radix_attn import Q_BITS
from repro.kernels.radix_conv import radix_conv2d_pallas
from repro.kernels.radix_matmul import (
    OCC_LANES,
    _project_levels,
    gated,
    int8_contract,
    mxu_dot,
    occ_mask,
    radix_matmul_pallas,
)
from repro.kernels.spike_encode import spike_encode_pallas

__all__ = [
    "KernelConfig",
    "Q_BITS",
    "radix_matmul",
    "radix_conv2d",
    "radix_decode_attention",
    "radix_encode",
    "epilogue_rows",
    "plane_occupancy",
    "same_pads",
]


def _interpret() -> bool:
    """Interpret mode exactly when JAX's default backend is the CPU: the
    kernels compile for whatever accelerator JAX found, and run in Python
    only on a host that has none.  A run meant for the chip must check
    the platform itself (``chip_smoke.py`` refuses anything but a TPU),
    since JAX falls back to the CPU when it finds no accelerator."""
    return jax.default_backend() == "cpu"


def _schedule(num_steps: Union[int, EncodingSpec]) -> KernelSchedule:
    """Accept a bare T or an :class:`EncodingSpec` wherever a kernel needs
    its plane schedule; returns the resolved :class:`KernelSchedule`.

    Specs must declare a kernel dataflow (the kernel epilogue implements
    their requantization: clip to the schedule's ``out_level``, then
    project onto its ``out_grid``); ``packed_bits`` is the bit-serial
    extraction width (phase: bits of ONE period) and ``periods`` the
    repeated-period replay count (phase: P; everything else: 1).  A bare
    integer T means the plain radix schedule.
    """
    if isinstance(num_steps, EncodingSpec):
        num_steps.validate_dataflow(None)   # declared + self-consistent
        return num_steps.kernel_schedule()
    return KernelSchedule(packed_bits=int(num_steps))


def _steps(num_steps: Union[int, EncodingSpec]) -> int:
    """Packed bit count of :func:`_schedule` (validates spec capability)."""
    return _schedule(num_steps).packed_bits


def plane_occupancy(
    x_q: jax.Array, num_bits: int
) -> Tuple[jax.Array, jax.Array]:
    """Per-bit-plane occupancy of packed activations (DESIGN.md §8).

    One bitwise-OR reduction over the whole tensor; bit ``s`` of the
    union is 1 iff *any* activation spikes on plane ``s``.  Returns
    ``(row, bits)``: ``row`` is the ``(1, OCC_LANES)`` int32 input the
    kernels consume (entry ``[0, s]`` gates the shift-``s`` plane pass),
    ``bits`` the bare ``(num_bits,)`` 0/1 vector — ``num_bits -
    bits.sum()`` is the number of plane passes a bitserial kernel skips
    (the fused dataflow masks the same bit lanes instead).
    """
    x = x_q.astype(jnp.int32)
    union = jax.lax.reduce(x, jnp.int32(0), jax.lax.bitwise_or,
                           tuple(range(x.ndim)))
    bits = (union >> jnp.arange(num_bits, dtype=jnp.int32)) & 1
    row = jnp.zeros((1, OCC_LANES), jnp.int32).at[0, :num_bits].set(bits)
    return row, bits


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _block(dim: int, pref: int = 128, align: int = 8):
    """(padded_dim, block) — full-dim single block for small sizes."""
    if dim >= pref:
        return _round_up(dim, pref), pref
    b = _round_up(dim, align)
    return b, b


def same_pads(size: int, k: int, stride: int) -> Tuple[int, int]:
    """(lo, hi) explicit pads matching XLA "SAME" for one spatial dim."""
    out = -(-size // stride)
    total = max((out - 1) * stride + k - size, 0)
    return total // 2, total - total // 2


def epilogue_rows(
    b_int: Optional[jax.Array],
    mult,
    n: int,
    n_pad: int,
    *,
    encoding: Optional[EncodingSpec] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fold (bias, requant multiplier) into kernel-epilogue row vectors.

    Returns ``(bias, mult)`` of shape ``(1, n_pad)``; the padding lanes get
    ``mult == 0`` so out-of-range output channels requantize to level 0 —
    which is what lets a compiled plan keep activations channel-padded
    between layers (core/engine).  ``encoding`` names the spec whose
    requantization the epilogue implements; it must be kernels-capable
    (the in-kernel clip targets its ``max_level`` == ``2^packed_bits - 1``).
    Period-repeated plane schedules (phase coding) need no row adjustment:
    the bitserial kernels divide the accumulator by ``periods`` *before*
    the bias/multiplier rows apply, exactly, so the rows always live in
    single-period accumulator units."""
    if encoding is not None:
        _schedule(encoding)   # validates kernel capability
    bias = jnp.zeros((n,), jnp.int32) if b_int is None \
        else jnp.asarray(b_int, jnp.int32).reshape(n)
    mrow = jnp.broadcast_to(
        jnp.asarray(mult, jnp.float32).reshape(-1), (n,))
    bias = jnp.pad(bias, (0, n_pad - n)).reshape(1, n_pad)
    mrow = jnp.pad(mrow, (0, n_pad - n)).reshape(1, n_pad)
    return bias, mrow


# ---------------------------------------------------------------------------
# XLA strategy twins: the same plane-pass math as the Pallas kernels
# (same occupancy gating, same fused epilogue floats -> bit-exact against
# the same oracles), but expressed as plain jitted XLA ops so the backend
# compiler picks the blocking.  On CPU — where Pallas runs in interpret
# mode and every grid step is Python overhead — this twin with
# ``mxu_dtype="f32"`` is what actually closes the gap to dense; the
# autotuner discovers that rather than hard-coding it.
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("num_steps", "method", "periods", "mxu_dtype",
                     "out_level", "out_grid", "acc_dtype"))
def _xla_matmul(x2, w2, bias, mult, occ, *, num_steps, method, periods=1,
                mxu_dtype="int8", out_level=None, out_grid="dense",
                acc_dtype="int32"):
    """Jitted XLA twin of ``radix_matmul_pallas`` (unpadded shapes)."""
    # ``mxu_dot`` lowers both operands itself, so the packed input and the
    # weight go in untouched on the fused path: under ``mxu_dtype="f32"``
    # the activation converts uint8 -> f32 directly (no int32 detour) and
    # a weight captured as a jit constant converts once at compile time —
    # that is what holds this twin at dense-GEMM speed.  The bit algebra
    # (occupancy masks, plane shifts) still needs an integer view.
    w = w2
    occ_row = occ[0] if occ is not None else None
    if method == "fused":
        x = x2
        if occ_row is not None:
            x = x.astype(jnp.int32) & occ_mask(occ_row, num_steps)
        acc = mxu_dot(x, w, mxu_dtype, acc_dtype, a_bits=num_steps)
    else:
        x = x2.astype(jnp.int32)
        zero = jnp.zeros((x.shape[0], w.shape[1]), jnp.int32)

        def plane(shift):
            p = (x >> shift) & 1
            return gated(occ_row, shift, lambda: mxu_dot(p, w, mxu_dtype),
                         zero)

        acc = zero
        if periods == 1:
            for t in range(num_steps):        # the paper's Horner schedule
                acc = (acc << 1) + plane(num_steps - 1 - t)
        else:
            for t in range(num_steps * periods):
                shift = num_steps - 1 - (t % num_steps)
                acc = acc + (plane(shift) << shift)
            acc = acc // periods
    if mult is None:
        return acc
    q = jnp.floor((acc + bias).astype(jnp.float32) * mult)
    return _project_levels(q, out_level=out_level, out_grid=out_grid)


def _conv_lowered(p, w, stride, mxu_dtype, acc_dtype="int32", a_bits=None):
    """One plane/packed conv under the selected lowering.  int32 out,
    except ``acc_dtype="f32"`` (the f32 boundary layout) keeps the
    exact-integer f32 accumulator — same contract as ``mxu_dot``, whose
    ``a_bits`` operand bound it shares."""
    def conv(a, b, pet):
        return jax.lax.conv_general_dilated(
            a, b, window_strides=(stride, stride), padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=pet)

    if mxu_dtype == "int8":
        return int8_contract(lambda a, b: conv(a, b, jnp.int32), p, w,
                             a_bits=a_bits)
    if mxu_dtype != "f32":
        raise ValueError(f"unknown mxu_dtype {mxu_dtype!r}")
    out = conv(p.astype(jnp.float32), w.astype(jnp.float32), jnp.float32)
    return out if acc_dtype == "f32" else out.astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("num_steps", "method", "stride", "periods", "mxu_dtype",
                     "out_level", "out_grid", "acc_dtype"))
def _xla_conv2d(x_q, w_q, bias, mult, occ, *, num_steps, method, stride=1,
                periods=1, mxu_dtype="int8", out_level=None,
                out_grid="dense", acc_dtype="int32"):
    """Jitted XLA twin of ``radix_conv2d_pallas`` (VALID, pre-padded)."""
    # same operand-lowering contract as ``_xla_matmul``: ``_conv_lowered``
    # casts per ``mxu_dtype``; only the bit algebra needs integer views
    w = w_q
    occ_row = occ[0] if occ is not None else None
    if method == "fused":
        x = x_q
        if occ_row is not None:
            x = x.astype(jnp.int32) & occ_mask(occ_row, num_steps)
        acc = _conv_lowered(x, w, stride, mxu_dtype, acc_dtype,
                            a_bits=num_steps)
    else:
        x = x_q.astype(jnp.int32)
        h_out = (x.shape[1] - w.shape[0]) // stride + 1
        w_out = (x.shape[2] - w.shape[1]) // stride + 1
        zero = jnp.zeros((x.shape[0], h_out, w_out, w.shape[3]), jnp.int32)

        def plane(shift):
            p = (x >> shift) & 1
            return gated(occ_row, shift,
                         lambda: _conv_lowered(p, w, stride, mxu_dtype),
                         zero)

        acc = zero
        if periods == 1:
            for t in range(num_steps):        # the paper's Horner schedule
                acc = (acc << 1) + plane(num_steps - 1 - t)
        else:
            for t in range(num_steps * periods):
                shift = num_steps - 1 - (t % num_steps)
                acc = acc + (plane(shift) << shift)
            acc = acc // periods
    if mult is None:
        return acc
    q = jnp.floor((acc + bias).astype(jnp.float32) * mult)
    return _project_levels(q, out_level=out_level, out_grid=out_grid)


# ---------------------------------------------------------------------------
# Strategy execution + autotune resolution.
# ---------------------------------------------------------------------------


def _resolve_config(config, autotune, sample, key_fn, cand_fn, build_fn):
    """Pick the strategy for one call: explicit ``config`` wins; else a
    tuned winner when ``autotune`` (sweeping only outside a jit trace —
    inside one, fall back to the already-cached winner or the default);
    else the untuned default."""
    if config is not None:
        return config
    if not autotune:
        return KernelConfig()
    if isinstance(sample, jax.core.Tracer):
        return autotune_mod.default_cache().get(key_fn()) or KernelConfig()
    return autotune_mod.tune(key_fn(), cand_fn(), build_fn)


def _matmul_with_config(cfg, x2, w_q, b_int, mult, sched, spec, method,
                        sparsity):
    """Execute one matmul strategy on (m, k) x (k, n) unpadded inputs."""
    num_steps, periods = sched.packed_bits, sched.periods
    m, k = x2.shape
    n = w_q.shape[-1]
    # occupancy reduces exactly from either layout (f32 levels are exact
    # small integers; plane_occupancy casts to int32 itself)
    occ = plane_occupancy(x2, num_steps)[0] if sparsity else None
    if cfg.act_dtype == "f32":
        if method != "fused" or cfg.impl != "xla":
            raise ValueError(
                "act_dtype='f32' is only legal on the fused XLA twin "
                "(bit-serial plane extraction needs the packed layout)")
        x2 = x2.astype(jnp.float32)   # no-op when the caller owns the layout
    # the f32 boundary layout keeps the accumulator in exact-integer f32
    # too (same mantissa gate): the int32 convert is an unfused extra
    # pass over the output that a strategy with an f32 boundary never
    # needs — raw callers get f32, the epilogue consumes f32 natively
    acc_dtype = "f32" if cfg.act_dtype == "f32" else "int32"

    if cfg.impl == "xla":
        if mult is None:
            out = _xla_matmul(x2, w_q, None, None, occ, num_steps=num_steps,
                              method=method, periods=periods,
                              mxu_dtype=cfg.mxu_dtype, acc_dtype=acc_dtype)
            return out if b_int is None else out + b_int
        bias_row, mult_row = epilogue_rows(b_int, mult, n, n, encoding=spec)
        return _xla_matmul(x2, w_q, bias_row, mult_row, occ,
                           num_steps=num_steps, method=method,
                           periods=periods, mxu_dtype=cfg.mxu_dtype,
                           out_level=sched.out_level,
                           out_grid=sched.out_grid, acc_dtype=acc_dtype)

    mp, bm = _block(m, pref=cfg.bm)
    kp, bk = _block(k, pref=cfg.bk)
    np_, bn = _block(n, pref=cfg.bn)
    xp = jnp.pad(x2, ((0, mp - m), (0, kp - k)))
    wp = jnp.pad(w_q, ((0, kp - k), (0, np_ - n)))
    pp = cfg.plane_parallel and method == "bitserial"
    if mult is None:
        out = radix_matmul_pallas(
            xp, wp, num_steps=num_steps, method=method,
            bm=bm, bk=bk, bn=bn, interpret=_interpret(), periods=periods,
            occupancy=occ, mxu_dtype=cfg.mxu_dtype, plane_parallel=pp,
        )[:m, :n]
        return out if b_int is None else out + b_int
    bias_row, mult_row = epilogue_rows(b_int, mult, n, np_, encoding=spec)
    return radix_matmul_pallas(
        xp, wp, num_steps=num_steps, method=method,
        bm=bm, bk=bk, bn=bn, interpret=_interpret(), periods=periods,
        bias=bias_row, mult=mult_row, occupancy=occ,
        out_level=sched.out_level, out_grid=sched.out_grid,
        mxu_dtype=cfg.mxu_dtype, plane_parallel=pp,
    )[:m, :n]


def radix_matmul(
    x_q: jax.Array,
    w_q: jax.Array,
    b_int: jax.Array | None,
    num_steps: Union[int, EncodingSpec],
    *,
    method: str = "bitserial",
    mult=None,
    sparsity: bool = False,
    autotune: bool = False,
    config: Optional[KernelConfig] = None,
) -> jax.Array:
    """(..., K) packed levels @ (K, N) int8 (+bias) -> (..., N).

    ``num_steps`` may be a bare T or a kernels-capable ``EncodingSpec``
    (whose packed bit count, period-repeat schedule and epilogue output
    grid are honored).  ``mult=None``: raw int32 accumulator (+bias
    outside the kernel).  ``mult`` given: fused output-logic epilogue ->
    packed uint8 levels.  ``sparsity=True`` runs the plane-occupancy
    prepass: bit planes no activation spikes on are skipped in-kernel
    (bitserial) or masked out of the packed pass (fused) — bit-exact,
    since empty planes contribute zero.  ``autotune=True`` times the
    legal strategies on these inputs and reuses the cached winner on
    repeat shapes; ``config=`` pins one explicitly (both bit-exact)."""
    sched = _schedule(num_steps)
    spec = num_steps if isinstance(num_steps, EncodingSpec) else None
    lead = x_q.shape[:-1]
    k = x_q.shape[-1]
    n = w_q.shape[-1]
    x2 = x_q.reshape(-1, k)
    m = x2.shape[0]

    cfg = _resolve_config(
        config, autotune, x2,
        key_fn=lambda: autotune_mod.matmul_key(
            m, k, n, sched, method, epilogue=mult is not None,
            sparsity=sparsity),
        cand_fn=lambda: autotune_mod.matmul_candidates(
            m, k, n, sched, method, interpret=_interpret()),
        build_fn=lambda c: (lambda: _matmul_with_config(
            c, x2, w_q, b_int, mult, sched, spec, method, sparsity)),
    )
    return _matmul_with_config(
        cfg, x2, w_q, b_int, mult, sched, spec, method, sparsity,
    ).reshape(*lead, n)


def _conv_with_config(cfg, x_q, w_q, b_int, mult, sched, spec, method,
                      stride, sparsity):
    """Execute one conv strategy on pre-padded NHWC x HWIO inputs."""
    num_steps, periods = sched.packed_bits, sched.periods
    cout = w_q.shape[-1]
    occ = plane_occupancy(x_q, num_steps)[0] if sparsity else None
    if cfg.act_dtype == "f32":
        if method != "fused" or cfg.impl != "xla":
            raise ValueError(
                "act_dtype='f32' is only legal on the fused XLA twin "
                "(bit-serial plane extraction needs the packed layout)")
        x_q = x_q.astype(jnp.float32)  # no-op when the caller owns the layout
    # same accumulator contract as the matmul twin: f32 boundary layout
    # -> exact-integer f32 accumulator, no unfused int32 convert pass
    acc_dtype = "f32" if cfg.act_dtype == "f32" else "int32"

    if cfg.impl == "xla":
        if mult is None:
            out = _xla_conv2d(x_q, w_q, None, None, occ,
                              num_steps=num_steps, method=method,
                              stride=stride, periods=periods,
                              mxu_dtype=cfg.mxu_dtype, acc_dtype=acc_dtype)
            return out if b_int is None else out + b_int
        bias_row, mult_row = epilogue_rows(b_int, mult, cout, cout,
                                           encoding=spec)
        return _xla_conv2d(x_q, w_q, bias_row, mult_row, occ,
                           num_steps=num_steps, method=method,
                           stride=stride, periods=periods,
                           mxu_dtype=cfg.mxu_dtype,
                           out_level=sched.out_level,
                           out_grid=sched.out_grid, acc_dtype=acc_dtype)

    cop, bco = _block(cout, pref=cfg.bco)
    w_p = jnp.pad(w_q, ((0, 0), (0, 0), (0, 0), (0, cop - cout)))
    pp = cfg.plane_parallel and method == "bitserial"
    if mult is None:
        out = radix_conv2d_pallas(
            x_q, w_p, num_steps=num_steps, method=method, bco=bco,
            stride=stride, interpret=_interpret(), periods=periods,
            occupancy=occ, mxu_dtype=cfg.mxu_dtype, plane_parallel=pp,
        )[..., :cout]
        return out if b_int is None else out + b_int
    bias_row, mult_row = epilogue_rows(b_int, mult, cout, cop, encoding=spec)
    return radix_conv2d_pallas(
        x_q, w_p, num_steps=num_steps, method=method, bco=bco,
        stride=stride, interpret=_interpret(), periods=periods,
        bias=bias_row, mult=mult_row, occupancy=occ,
        out_level=sched.out_level, out_grid=sched.out_grid,
        mxu_dtype=cfg.mxu_dtype, plane_parallel=pp,
    )[..., :cout]


def radix_conv2d(
    x_q: jax.Array,
    w_q: jax.Array,
    b_int: jax.Array | None,
    num_steps: Union[int, EncodingSpec],
    *,
    stride: int = 1,
    padding: str = "VALID",
    method: str = "bitserial",
    mult=None,
    sparsity: bool = False,
    autotune: bool = False,
    config: Optional[KernelConfig] = None,
) -> jax.Array:
    """NHWC packed levels * HWIO int8 -> NHWC conv (+bias).

    ``num_steps`` may be a bare T or a kernels-capable ``EncodingSpec``
    (whose packed bit count, period-repeat schedule and epilogue output
    grid are honored).  SAME padding is pre-padded (XLA-exact pads for
    any stride); stride > 1 subsamples *inside* the kernel grid — only
    the h_out x w_out surviving outputs are ever computed.  ``mult``
    turns on the fused output-logic epilogue (packed uint8 levels out);
    ``sparsity=True`` runs the plane-occupancy prepass (empty planes
    skipped/masked in-kernel, bit-exact).  ``autotune=True`` times the
    legal strategies on these inputs and reuses the cached winner on
    repeat shapes; ``config=`` pins one explicitly (both bit-exact)."""
    sched = _schedule(num_steps)
    spec = num_steps if isinstance(num_steps, EncodingSpec) else None
    kh, kw, cin, cout = w_q.shape
    if padding == "SAME":
        ph = same_pads(x_q.shape[1], kh, stride)
        pw = same_pads(x_q.shape[2], kw, stride)
        x_q = jnp.pad(x_q, ((0, 0), ph, pw, (0, 0)))
    elif padding != "VALID":
        raise ValueError(padding)

    cfg = _resolve_config(
        config, autotune, x_q,
        key_fn=lambda: autotune_mod.conv_key(
            x_q.shape[1], x_q.shape[2], cin, kh, kw, cout, stride, sched,
            method, batch=x_q.shape[0], epilogue=mult is not None,
            sparsity=sparsity),
        cand_fn=lambda: autotune_mod.conv_candidates(
            x_q.shape[1], x_q.shape[2], cin, kh, kw, cout, sched, method,
            interpret=_interpret()),
        build_fn=lambda c: (lambda: _conv_with_config(
            c, x_q, w_q, b_int, mult, sched, spec, method, stride,
            sparsity)),
    )
    return _conv_with_config(cfg, x_q, w_q, b_int, mult, sched, spec,
                             method, stride, sparsity)


# ---------------------------------------------------------------------------
# Packed decode attention: the blockwise online-softmax kernel over the
# radix KV cache (kernels/radix_attn.py) plus its jitted XLA twin — the
# same plane-weight QK^T algebra, scale-folded streaming softmax, and
# occupancy gating, expressed as batched XLA dots.  On CPU (interpret-mode
# Pallas) the twin is what the autotuner picks; the differential suite
# (tests/test_attn_differential.py) pins both to the ref.py oracle.
# ---------------------------------------------------------------------------


def _attn_bdot(a, b, mxu_dtype, *, a_bits=None, b_bits=None):
    """(N, g, d) x (N, blk, d) -> (N, g, blk) int32 batched contraction
    under the selected lowering (``mxu_dot``'s contract, batched)."""
    dn = (((2,), (2,)), ((0,), (0,)))
    if mxu_dtype == "int8":
        return int8_contract(
            lambda x, y: jax.lax.dot_general(
                x, y, dn, preferred_element_type=jnp.int32),
            a, b, a_bits=a_bits, b_bits=b_bits)
    if mxu_dtype == "f32":
        return jax.lax.dot_general(
            a.astype(jnp.float32), b.astype(jnp.float32), dn,
            preferred_element_type=jnp.float32).astype(jnp.int32)
    raise ValueError(f"unknown mxu_dtype {mxu_dtype!r}")


def _attn_bdot_f32(p, v):
    """(N, g, blk) f32 x (N, blk, hd) -> (N, g, hd) f32 value pass."""
    return jax.lax.dot_general(
        p.astype(jnp.float32), v.astype(jnp.float32),
        (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("num_steps", "q_bits", "hd", "method", "packed",
                     "blk", "mxu_dtype", "sparsity"))
def _xla_decode_attn(qq, qs, kq, ks, vq, vs, mask, occ_k, occ_v, *,
                     num_steps, q_bits, hd, method, packed, blk,
                     mxu_dtype="int8", sparsity=True):
    """Jitted XLA twin of ``radix_decode_attn_pallas`` (same (N = B*Hkv)
    row layout, S pre-padded to a ``blk`` multiple).  Processes the cache
    blockwise through the shared online-softmax core — only the current
    block's levels are ever unpacked, so the full dequantized float K/V
    never materializes here either."""
    n, g, hdq = qq.shape
    s_len = kq.shape[1]
    lvl = (1 << num_steps) - 1
    occk = occ_k[0] if sparsity else None
    occv = occ_v[0] if sparsity else None
    qsf = qs[..., None]                                   # (n, g, 1)
    qsum = jnp.sum(qq.astype(jnp.int32), axis=-1, keepdims=True)
    state = radix_attn.osm_init((n, g, 1), (n, g, hdq))

    for j0 in range(0, s_len, blk):
        kb = radix_attn.unpack_levels(kq[:, j0:j0 + blk], packed)
        vb = radix_attn.unpack_levels(vq[:, j0:j0 + blk], packed)
        skb = ks[:, None, j0:j0 + blk]                    # (n, 1, blk)
        svb = vs[:, None, j0:j0 + blk]
        mb = mask[:, None, j0:j0 + blk] > 0

        if method == "fused":
            kb_m = kb if occk is None else kb & occ_mask(occk, num_steps)
            sint = _attn_bdot(qq, kb_m, mxu_dtype, a_bits=q_bits,
                              b_bits=num_steps)
        else:
            zero = jnp.zeros((n, g, kb.shape[1]), jnp.int32)
            sint = zero
            for s in range(num_steps):
                plane = (kb >> s) & 1
                sint = sint + (gated(
                    occk, s,
                    lambda plane=plane: _attn_bdot(qq, plane, mxu_dtype,
                                                   a_bits=q_bits),
                    zero) << s)
        ksum = jnp.sum(kb, axis=-1)[:, None, :]           # (n, 1, blk)
        scores = radix_attn.plane_scores(
            sint, qsum, ksum, qsf, skb, hd=hd, num_steps=num_steps,
            q_bits=q_bits)

        def pv(p, vb=vb, svb=svb):
            pw = p * svb                                  # fold v scales
            if method == "fused":
                vb_m = vb if occv is None else vb & occ_mask(occv, num_steps)
                vint = _attn_bdot_f32(pw, vb_m)
            else:
                zf = jnp.zeros((n, g, hdq), jnp.float32)
                vint = zf
                for s in range(num_steps):
                    plane = (vb >> s) & 1
                    vint = vint + gated(
                        occv, s,
                        lambda plane=plane: _attn_bdot_f32(pw, plane),
                        zf) * float(1 << s)
            return (2.0 / lvl) * vint - jnp.sum(pw, axis=-1, keepdims=True)

        state = radix_attn.osm_update(state, scores, mb, pv)
    return radix_attn.osm_finalize(state)


def _nibble_union(levels: jax.Array) -> jax.Array:
    """Per-byte OR of hi/lo nibbles — the occupancy view of a packed
    cache (plane_occupancy's OR-reduction over it equals occupancy of
    the unpacked levels, without materializing them)."""
    return jnp.bitwise_or(levels >> 4, levels & 0xF)


def _attn_with_config(cfgk, qq, qs, kq, ks, vq, vs, mask, occ_k, occ_v, *,
                      num_steps, q_bits, hd, method, packed, sparsity):
    """Execute one decode-attention strategy on (N, ...) laid-out inputs."""
    n, g, hdq = qq.shape
    s_len = kq.shape[1]
    sp, blk = _block(s_len, pref=cfgk.bk)
    if sp > s_len:
        pad = sp - s_len
        kq = jnp.pad(kq, ((0, 0), (0, pad), (0, 0)))
        vq = jnp.pad(vq, ((0, 0), (0, pad), (0, 0)))
        ks = jnp.pad(ks, ((0, 0), (0, pad)))
        vs = jnp.pad(vs, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))      # padded slots masked

    if cfgk.impl == "xla":
        return _xla_decode_attn(
            qq, qs, kq, ks, vq, vs, mask, occ_k, occ_v,
            num_steps=num_steps, q_bits=q_bits, hd=hd, method=method,
            packed=packed, blk=blk, mxu_dtype=cfgk.mxu_dtype,
            sparsity=sparsity)

    gp = _round_up(g, 8)
    if gp > g:
        qq = jnp.pad(qq, ((0, 0), (0, gp - g), (0, 0)))
        qs = jnp.pad(qs, ((0, 0), (0, gp - g)), constant_values=1.0)
    out = radix_attn.radix_decode_attn_pallas(
        qq, qs, kq, ks, vq, vs, mask, occ_k, occ_v,
        num_steps=num_steps, q_bits=q_bits, hd=hd, method=method,
        packed=packed, blk=blk, mxu_dtype=cfgk.mxu_dtype,
        sparsity=sparsity, interpret=_interpret())
    return out[:, :g]


def radix_decode_attention(
    q: jax.Array,
    k_q: jax.Array,
    k_scale: jax.Array,
    v_q: jax.Array,
    v_scale: jax.Array,
    mask: jax.Array,
    num_steps: int,
    *,
    packed: bool = False,
    method: str = "bitserial",
    q_bits: int = Q_BITS,
    sparsity: bool = True,
    autotune: bool = False,
    config: Optional[KernelConfig] = None,
) -> jax.Array:
    """Blockwise decode attention directly over the radix KV cache.

    ``q`` (B, H, hd) float decode queries (post-RoPE); ``k_q``/``v_q``
    (B, S, Hkv, hd) uint8 cache levels — or (B, S, Hkv, hd//2) when
    ``packed`` (two nibble levels per byte); ``k_scale``/``v_scale``
    (B, S, Hkv) f32 per-(token, head) scales; ``mask`` (B, S) boolean
    slot validity (full causal or ring-buffer window — softmax over
    cache *slots* is permutation-invariant, so ring order needs no
    unrotation).  Returns the (B, H, hd) f32 attention output (pre
    out-projection).  Never materializes a dequantized float K/V: the
    query is radix-quantized (``q_bits``), QK^T runs as occupancy-gated
    integer plane algebra, and the per-token scales fold into the
    streaming online softmax (kernels/radix_attn.py).

    ``autotune=True`` sweeps the legal ``KernelConfig`` strategies
    (Pallas KV-block tiles x dot lowerings, plus the XLA twin) and bakes
    the winner per ``autotune.attn_key``; ``config=`` pins one.  All
    strategies agree to f32 rounding (the integer dots are bit-exact;
    the float softmax reassociates across block sizes)."""
    B, H, hd = q.shape
    s_len, hkv = k_q.shape[1], k_q.shape[2]
    g = H // hkv
    assert g * hkv == H, (H, hkv)
    n = B * hkv

    qq, qscale = radix_attn.quantize_q(q, q_bits)     # (B, H, hd), (B, H, 1)
    qq = qq.reshape(B, hkv, g, hd).reshape(n, g, hd)
    qs = qscale.reshape(B, hkv, g).reshape(n, g)
    if packed:
        perm = list(range(0, hd, 2)) + list(range(1, hd, 2))
        qq = qq[..., jnp.asarray(perm)]

    def seq_major(a):                     # (B, S, Hkv, ...) -> (N, S, ...)
        moved = jnp.moveaxis(a, 2, 1)
        return moved.reshape((n,) + moved.shape[2:])

    kq = seq_major(k_q)
    vq = seq_major(v_q)
    ks = seq_major(k_scale)
    vs = seq_major(v_scale)
    maskn = jnp.broadcast_to(mask[:, None, :], (B, hkv, s_len))
    maskn = maskn.reshape(n, s_len).astype(jnp.int32)

    if sparsity:
        occ_src_k = _nibble_union(k_q) if packed else k_q
        occ_src_v = _nibble_union(v_q) if packed else v_q
        occ_k = plane_occupancy(occ_src_k, num_steps)[0]
        occ_v = plane_occupancy(occ_src_v, num_steps)[0]
    else:
        occ_k = jnp.ones((1, OCC_LANES), jnp.int32)
        occ_v = jnp.ones((1, OCC_LANES), jnp.int32)

    cfgk = _resolve_config(
        config, autotune, q,
        key_fn=lambda: autotune_mod.attn_key(
            B, s_len, hkv, g, hd, num_steps, method, q_bits=q_bits,
            packed=packed, sparsity=sparsity),
        cand_fn=lambda: autotune_mod.attn_candidates(
            s_len, hd, num_steps, method, q_bits=q_bits,
            interpret=_interpret()),
        build_fn=lambda c: (lambda: _attn_with_config(
            c, qq, qs, kq, ks, vq, vs, maskn, occ_k, occ_v,
            num_steps=num_steps, q_bits=q_bits, hd=hd, method=method,
            packed=packed, sparsity=sparsity)),
    )
    out = _attn_with_config(
        cfgk, qq, qs, kq, ks, vq, vs, maskn, occ_k, occ_v,
        num_steps=num_steps, q_bits=q_bits, hd=hd, method=method,
        packed=packed, sparsity=sparsity)

    if packed:
        perm = list(range(0, hd, 2)) + list(range(1, hd, 2))
        inv = [0] * hd
        for i, p_ in enumerate(perm):
            inv[p_] = i
        out = out[..., jnp.asarray(inv)]
    return out.reshape(B, hkv, g, hd).reshape(B, H, hd)


def radix_encode(
    x: jax.Array, num_steps: Union[int, EncodingSpec], scale: float = 1.0
) -> jax.Array:
    """float -> packed radix levels (uint8), any shape."""
    num_steps = _steps(num_steps)
    lead = x.shape
    x2 = x.reshape(-1, lead[-1]) if x.ndim > 1 else x.reshape(1, -1)
    r, c = x2.shape
    rp, br = _block(r, pref=256)
    x2 = jnp.pad(x2, ((0, rp - r), (0, 0)))
    out = spike_encode_pallas(
        x2, num_steps=num_steps, scale=float(scale), br=br,
        interpret=_interpret(),
    )[:r]
    return out.reshape(lead)
