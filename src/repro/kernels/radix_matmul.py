"""Pallas TPU kernel: radix (bit-serial) matmul with Horner accumulation.

The paper's convolution/linear units consume binary spike planes and
accumulate with a one-bit left shift between time steps.  On TPU the packed
activation (uint8 level in [0, 2^T - 1]) stays resident in VMEM while all T
bit-planes are processed — the VMEM-residency analogue of the FPGA's
shift-register reuse (DESIGN.md §2).

Two in-kernel strategies, selected statically:

* ``method="bitserial"`` — paper-faithful: T plane-extract + int matmul
  passes, Horner-combined.  One MXU pass per time step, activations read
  once (1 byte/element).
* ``method="fused"``    — beyond-paper TPU-native: by the radix identity
  ``sum_t 2^(T-1-t) plane_t == x_q``, the whole spike train collapses into a
  SINGLE int8 MXU matmul.  T× fewer MXU passes, same bits out.  This is the
  optimization the FPGA cannot make (no multipliers) but the MXU gets for
  free — the central hardware-adaptation insight of this reproduction.

Sparsity-aware execution (DESIGN.md §8, docs/kernels.md)
--------------------------------------------------------
Passing ``occupancy`` (a ``(1, OCC_LANES)`` int32 row whose entry ``s``
is 1 iff any activation spikes on bit plane ``s`` — ``ops.plane_occupancy``
computes it in one bitwise-OR reduction) turns on the plane-occupancy
schedule: the bitserial loop wraps each plane pass in a ``lax.cond`` and
**skips the MXU pass entirely** when the plane is globally empty (the
dynamic early-exit temporal codes like TTFS are built for — one spike per
activation means most planes are empty for narrow value distributions),
while the fused path ANDs the packed levels with the occupancy bit mask
(a masked pass — empty bit lanes are provably zero, so this is exact).

Fused epilogue (DESIGN.md §2)
-----------------------------
Passing ``bias``/``mult`` turns on the in-kernel *output logic*: on the
last K-grid step the int32 accumulator (kept in a VMEM scratch tile, never
written to HBM) gets bias-add, the requantization multiply
(``layers.q_requantize`` semantics, bit-exact), and a clamp to
``[0, out_level]`` — and the kernel emits **packed uint8 levels** directly.
``out_grid="pow2"`` additionally floors the clamped level onto the
power-of-two grid ``{0} | {2^k}`` (``encoding.pow2_floor``), which is the
TTFS output logic: the layer re-times exactly one output spike, in-kernel.
This is the TPU twin of the paper's output unit writing T-bit activations
straight into the pong buffer: inter-layer HBM traffic drops 4×
(1 byte/element instead of a 4-byte raw accumulator), and the separate
bias/requantize/re-encode XLA ops (each a fresh HBM round trip) disappear.
The epilogue-free int32 path remains for the final logits layer.

Grid: (M/bm, N/bn, K/bk), K innermost ("arbitrary" semantics) accumulating
into a VMEM tile which Pallas keeps revisiting.
"""

from __future__ import annotations

import functools
from typing import Literal, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "OCC_LANES",
    "int8_contract",
    "mxu_dot",
    "radix_matmul_pallas",
]

OCC_LANES = 128
"""Lane-aligned width of the plane-occupancy row the kernels consume
(entries beyond the actual bit count are ignored)."""

INT8_SLICE_BITS = 7
"""Bits of a non-negative operand that one int8 MXU pass carries."""


def occ_mask(occ, num_steps: int) -> jax.Array:
    """Bit mask of the occupied planes (``Σ occ[s] << s``) — the fused
    dataflow's masked-pass operand.  Shared by the matmul and conv
    kernels so the gating algebra cannot drift between them."""
    mask = jnp.int32(0)
    for s in range(num_steps):
        mask = mask | (occ[s] << s)
    return mask


def gated(occ, shift, fn, zero) -> jax.Array:
    """One occupancy-gated plane pass: run ``fn()`` only when plane
    ``shift`` is occupied, else return the ``zero`` tile (``occ=None``
    means ungated).  The ``lax.cond`` is the bitserial dynamic
    early-exit.  ``shift`` may be traced (plane-parallel grids take it
    from ``pl.program_id``); the TPU lowering cannot slice a vector at a
    traced index, so that bit is read with a masked lane reduction."""
    if occ is None:
        return fn()
    if isinstance(shift, int):
        bit = occ[shift]
    else:
        lanes = jax.lax.broadcasted_iota(jnp.int32, occ.shape, 0)
        bit = jnp.max(jnp.where(lanes == shift, occ, 0))
    return jax.lax.cond(bit > 0, fn, lambda: zero)


def _int8_slices(x, bits: Optional[int]):
    """``(shift, int8 slice)`` pieces of a non-negative integer operand.

    ``bits=None`` (or ``<= 7``) means the operand already fits int8 —
    int8 weights, plane bits, packed levels with ``T <= 7`` — and it goes
    in whole.  A wider operand (packed levels with ``T >= 8``, a sum-pool
    carry) is cut into 7-bit slices, each of which fits int8."""
    if bits is None or bits <= INT8_SLICE_BITS:
        return [(0, x.astype(jnp.int8))]
    xi = x.astype(jnp.int32)
    mask = (1 << INT8_SLICE_BITS) - 1
    return [(s, ((xi >> s) & mask).astype(jnp.int8))
            for s in range(0, bits, INT8_SLICE_BITS)]


def int8_contract(contract, a, b, *, a_bits: Optional[int] = None,
                  b_bits: Optional[int] = None) -> jax.Array:
    """Exact integer contraction as int8 x int8 -> int32 MXU passes.

    ``contract(a8, b8)`` is the int8 contraction (``dot_general`` or
    ``conv_general_dilated`` with ``preferred_element_type=int32``);
    ``a_bits``/``b_bits`` bound non-negative operands (see
    :func:`_int8_slices`).  Operands that fit int8 take one pass; a
    wider one takes one pass per 7-bit slice, shifted back into place in
    int32 — the same sum, so exact wherever an int32 accumulation is."""
    acc = None
    for sa, a8 in _int8_slices(a, a_bits):
        for sb, b8 in _int8_slices(b, b_bits):
            part = contract(a8, b8)
            if sa + sb:
                part = part << (sa + sb)
            acc = part if acc is None else acc + part
    return acc


def mxu_dot(a, w, mxu_dtype: str = "int8", acc_dtype: str = "int32", *,
            a_bits: Optional[int] = None) -> jax.Array:
    """One plane/packed contraction under the selected MXU lowering.

    ``"int8"`` (the default) feeds the MXU int8 operands with
    ``preferred_element_type=int32`` — the lowering the TPU compiles, at
    the full int8 systolic rate.  It is always exact: ``a_bits`` bounds
    the activation operand, and one wider than int8 is sliced
    (:func:`int8_contract`).  ``"f32"`` runs the dot at the BLAS float
    rate — exact while every partial sum stays under the 24-bit f32
    mantissa (guarded by ``autotune.exact_lowering``); the XLA twin's
    winner on CPU, where XLA has no vectorized integer GEMM.  Every
    branch casts its own operands, so callers may hand raw packed/int8
    tensors or operands already held in the lowering dtype (a weight
    captured in a jitted plan converts once, at compile time).  The
    result is int32, except that ``acc_dtype="f32"`` (legal only with
    ``mxu_dtype="f32"``, i.e. the ``act_dtype="f32"`` boundary layout)
    keeps the exact-integer f32 accumulator."""
    dn = (((1,), (0,)), ((), ()))
    if mxu_dtype == "int8":
        return int8_contract(
            lambda x, y: jax.lax.dot_general(
                x, y, dn, preferred_element_type=jnp.int32),
            a, w, a_bits=a_bits)
    if mxu_dtype == "f32":
        out = jax.lax.dot_general(
            a.astype(jnp.float32), w.astype(jnp.float32), dn,
            preferred_element_type=jnp.float32)
        return out if acc_dtype == "f32" else out.astype(jnp.int32)
    raise ValueError(f"unknown mxu_dtype {mxu_dtype!r}")


def _accumulate_tile(x, w, *, num_steps: int, method: str,
                     periods: int = 1, occ=None,
                     mxu_dtype: str = "int8") -> jax.Array:
    """(bm, bk) x (bk, bn) int32 partial product, bit-serial or single-pass.

    ``periods > 1`` (phase coding) replays the ``num_steps`` plane passes
    ``periods`` times with the tiled weight schedule ``2^(T-1-(t mod T))``
    and divides the accumulator back down — exact, since the sum is
    ``periods ×`` the single-period value.  The fused path is unaffected:
    the radix identity already collapses one period into the packed level.

    ``occ`` (per-bit occupancy values, indexable by shift) gates each
    bitserial plane pass behind a ``lax.cond`` — an empty plane's MXU pass
    never executes — and masks the fused pass's packed bits.  Exact either
    way: a globally empty plane contributes zero.
    """

    def dot(a, bits=None):
        return mxu_dot(a, w, mxu_dtype, a_bits=bits)

    if method == "fused":
        # radix identity: one int MXU pass over packed levels
        if occ is not None:
            x = x & occ_mask(occ, num_steps)   # masked pass: occupied bits
        return dot(x, num_steps)

    zero = jnp.zeros((x.shape[0], w.shape[1]), jnp.int32)

    def plane_dot(shift):
        plane = (x >> shift) & 1               # gate: spike present or not
        # dynamic early-exit: the MXU pass runs only for occupied planes
        return gated(occ, shift, lambda: dot(plane), zero)

    acc = zero
    if periods == 1:
        # paper-faithful bit-serial Horner loop (T static, unrolled)
        for t in range(num_steps):
            acc = (acc << 1) + plane_dot(num_steps - 1 - t)
        return acc
    # phase schedule: all periods * T time steps, per-phase weights
    for t in range(num_steps * periods):
        shift = num_steps - 1 - (t % num_steps)
        acc = acc + (plane_dot(shift) << shift)
    return acc // periods


def _project_levels(q, *, out_level: int, out_grid: str) -> jax.Array:
    """Clamp a requantized float tile onto the schedule's level grid.

    ``"dense"``: ``clip(q, 0, out_level)``.  ``"pow2"``: the clip, then
    THE ``encoding.pow2_floor`` projection (one shared implementation, so
    the TTFS spec/ref/kernel twins cannot drift apart) — its where-chain
    traces fine inside a Pallas kernel body."""
    from repro.core.encoding import pow2_floor   # deferred: keep kernels
    #                                              importable standalone
    lvl = jnp.clip(q, 0, out_level).astype(jnp.int32)
    if out_grid == "pow2":
        lvl = pow2_floor(lvl, out_level.bit_length())
    elif out_grid != "dense":
        raise ValueError(f"unknown out_grid {out_grid!r}")
    return lvl.astype(jnp.uint8)


def _accumulate_step(x_ref, w_ref, occ, acc_ref, *, num_steps, method,
                     periods, mxu_dtype):
    """K-grid accumulation body: all plane passes in one grid step."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.int32)          # (bm, bk) packed levels
    acc_ref[...] += _accumulate_tile(x, w_ref[...], num_steps=num_steps,
                                     method=method, periods=periods,
                                     occ=occ, mxu_dtype=mxu_dtype)


def _plane_step(x_ref, w_ref, occ, acc_ref, *, num_steps, mxu_dtype):
    """Plane-parallel accumulation body: one grid step = ONE plane pass.

    The plane index ``t`` is grid dimension 3 (innermost), so the weight
    block — whose index map ignores ``t`` — stays resident across all
    ``T x periods`` plane passes: weight-stationary scheduling, one VMEM
    weight load amortized over the whole spike train instead of per
    Horner iteration.  The Horner recurrence is replaced by the additive
    form ``acc += (plane_t @ w) << shift_t`` (the same sum, reassociated
    — exact in int32), because grid steps cannot carry the
    multiply-by-two dependency chain."""
    t_idx = pl.program_id(3)

    @pl.when((pl.program_id(2) == 0) & (t_idx == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.int32)          # (bm, bk) packed levels
    w = w_ref[...]                            # (bk, bn) int8 weights
    shift = num_steps - 1 - jax.lax.rem(t_idx, num_steps)
    plane = (x >> shift) & 1
    zero = jnp.zeros((x.shape[0], w.shape[1]), jnp.int32)
    acc_ref[...] += gated(occ, shift,
                          lambda: mxu_dot(plane, w, mxu_dtype) << shift,
                          zero)


def _matmul_kernel(*refs, num_steps, method, periods, out_level, out_grid,
                   mxu_dtype, sparse, epilogue, plane_parallel):
    """One (bm, bk) x (bk, bn) tile[, one plane] of the (M, N, K[, plane])
    grid.

    Refs, in order: x, w, [occupancy], [bias, mult], out, [accumulator
    scratch].  The int32 sum accumulates across the K (and plane) grid in
    the output block itself (raw path) or in the VMEM scratch tile
    (epilogue path), where on the final visit the output logic — bias +
    requant multiply + clamp + level-grid projection, identical float
    ops to ``layers.q_requantize`` — runs in-register and only the packed
    uint8 level reaches HBM.  Occupancy-gated plane passes skip when
    their occupancy bit is 0 (bitserial) / packed bits mask to the
    occupied lanes (fused)."""
    refs = list(refs)
    x_ref, w_ref = refs.pop(0), refs.pop(0)
    occ = refs.pop(0)[0] if sparse else None
    bias_ref, mult_ref = (refs.pop(0), refs.pop(0)) if epilogue else (
        None, None)
    o_ref = refs.pop(0)
    acc_ref = refs.pop(0) if epilogue else o_ref
    last = pl.program_id(2) == pl.num_programs(2) - 1
    if plane_parallel:
        _plane_step(x_ref, w_ref, occ, acc_ref, num_steps=num_steps,
                    mxu_dtype=mxu_dtype)
        last = last & (pl.program_id(3) == num_steps * periods - 1)
    else:
        _accumulate_step(x_ref, w_ref, occ, acc_ref, num_steps=num_steps,
                         method=method, periods=periods, mxu_dtype=mxu_dtype)
    # the sequential path divides the phase replay back down per tile;
    # plane-parallel steps can only do it on the final visit
    divide = plane_parallel and periods > 1
    if not (epilogue or divide):
        return

    @pl.when(last)
    def _finish():
        if divide:
            acc_ref[...] = acc_ref[...] // periods
        if epilogue:
            acc = acc_ref[...] + bias_ref[...]        # (bm,bn) + (1,bn)
            q = jnp.floor(acc.astype(jnp.float32) * mult_ref[...])
            o_ref[...] = _project_levels(q, out_level=out_level,
                                         out_grid=out_grid)


@functools.partial(
    jax.jit,
    static_argnames=("num_steps", "method", "bm", "bk", "bn", "interpret",
                     "out_steps", "periods", "out_level", "out_grid",
                     "mxu_dtype", "plane_parallel"),
)
def radix_matmul_pallas(
    x_q: jax.Array,
    w_q: jax.Array,
    *,
    num_steps: int,
    method: Literal["bitserial", "fused"] = "bitserial",
    bm: int = 128,
    bk: int = 128,
    bn: int = 128,
    interpret: bool = False,
    bias: Optional[jax.Array] = None,
    mult: Optional[jax.Array] = None,
    out_steps: Optional[int] = None,
    periods: int = 1,
    out_level: Optional[int] = None,
    out_grid: str = "dense",
    occupancy: Optional[jax.Array] = None,
    mxu_dtype: str = "int8",
    plane_parallel: bool = False,
) -> jax.Array:
    """(M, K) uint8 levels @ (K, N) int8 -> (M, N).

    Without ``mult``: raw int32 accumulators (the logits-layer path).
    With ``mult`` (f32 ``(1, N)``) and optional ``bias`` (int32 ``(1, N)``):
    the fused output-logic epilogue runs in-kernel and the result is packed
    uint8 levels in ``[0, out_level]``.  ``num_steps`` governs the
    bit-serial input extraction; ``out_level`` (default ``2^out_steps - 1``,
    ``out_steps`` defaulting to ``num_steps``) the output clamp — they
    differ when inputs carry extra integer bits, e.g. after a sum-pool
    whose division is folded into ``mult``.  ``out_grid`` selects the
    epilogue's level grid per the encoding's ``KernelSchedule`` ("dense"
    clip, or "pow2" for TTFS's log-spaced re-timing).  ``periods`` (phase
    coding, bitserial only) replays the plane schedule that many times
    with tiled per-phase weights and an exact in-kernel divide.
    ``occupancy`` (``(1, OCC_LANES)`` int32, from ``ops.plane_occupancy``)
    turns on the sparsity-aware schedule: globally empty planes are
    skipped (bitserial) or masked (fused), bit-exactly.

    ``mxu_dtype`` selects the per-plane dot lowering (see ``mxu_dot``;
    the autotuner only picks non-default lowerings it can prove exact).
    ``plane_parallel`` (bitserial only) moves the plane loop into its
    own innermost grid dimension under weight-stationary block specs:
    the weight tile's index map ignores the plane index, so one weight
    load serves all ``T x periods`` plane passes and the passes become
    independently schedulable grid steps instead of an unrolled
    dependency chain.

    Shapes must be multiples of the block sizes (ops.py pads).
    Block sizes default to MXU-aligned 128s; VMEM footprint per step is
    bm*bk (x) + bk*bn (w) + bm*bn*4 (acc) bytes.
    """
    m, k = x_q.shape
    k2, n = w_q.shape
    assert k == k2, (x_q.shape, w_q.shape)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (
        f"shapes {(m, k, n)} not multiples of blocks {(bm, bk, bn)}")
    if plane_parallel and method != "bitserial":
        raise ValueError("plane_parallel requires method='bitserial' "
                         "(the fused dataflow has a single pass)")

    grid = (m // bm, n // bn, k // bk)
    if plane_parallel:
        # grid dim 3 = plane index, innermost: the weight block (index
        # map ignores t) stays resident across the whole spike train.
        grid += (num_steps * periods,)

    def spec(block, index):
        return pl.BlockSpec(block, lambda i, j, kk, *t: index(i, j, kk))

    specs = [spec((bm, bk), lambda i, j, kk: (i, kk)),
             spec((bk, bn), lambda i, j, kk: (kk, j))]
    args = [x_q, w_q]
    sparse = occupancy is not None
    if sparse:
        assert occupancy.shape == (1, OCC_LANES), occupancy.shape
        specs.append(spec((1, OCC_LANES), lambda i, j, kk: (0, 0)))
        args.append(occupancy.astype(jnp.int32))
    epilogue = mult is not None
    scratch = []
    if epilogue:
        out_steps = num_steps if out_steps is None else out_steps
        out_level = (1 << out_steps) - 1 if out_level is None else out_level
        assert out_level <= 255, (
            "packed uint8 epilogue requires out_level <= 255")
        if bias is None:
            bias = jnp.zeros((1, n), jnp.int32)
        assert bias.shape == (1, n) and mult.shape == (1, n), (
            bias.shape, mult.shape)
        row = spec((1, bn), lambda i, j, kk: (0, j))
        specs += [row, row]
        args += [bias, mult.astype(jnp.float32)]
        scratch = [pltpu.VMEM((bm, bn), jnp.int32)]

    kernel = functools.partial(
        _matmul_kernel, num_steps=num_steps, method=method, periods=periods,
        out_level=out_level, out_grid=out_grid, mxu_dtype=mxu_dtype,
        sparse=sparse, epilogue=epilogue, plane_parallel=plane_parallel)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=specs,
        out_specs=spec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n),
                                       jnp.uint8 if epilogue else jnp.int32),
        scratch_shapes=scratch,
        interpret=interpret,
    )(*args)
