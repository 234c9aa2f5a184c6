"""Pallas TPU kernel: radix (bit-serial) 2-D convolution, row-based dataflow.

TPU adaptation of the paper's convolution unit (Fig. 2):

* FPGA: an input *row* lives in a shift register; kernel rows stream through
  a Y x X adder array; partial sums propagate down; time steps Horner-merge
  in the output logic.
* TPU: a *band* of input rows (the output row tile plus its ``kh - stride``
  row halo, all W positions, all input channels, whole T-packed byte per
  activation) lives in VMEM; the kernel-row/column loops are static
  unrolls around MXU matmuls over the input-channel dim; time steps
  Horner-merge in an int32 register tile.

Strided convolutions subsample *inside* the kernel: each (kh, kw) tap
gathers only the rows/columns that land on the stride grid, so the kernel
computes exactly ``h_out x w_out`` outputs instead of materializing the
stride-1 result and discarding (stride^2 - 1)/stride^2 of it.

Sparsity-aware execution (DESIGN.md §8, docs/kernels.md): passing
``occupancy`` (the ``(1, OCC_LANES)`` row ``ops.plane_occupancy`` builds)
gates every bitserial plane pass behind a ``lax.cond`` — a globally empty
spike plane's entire (kh x kw x Cin) tap sweep never executes — and masks
the fused pass's packed bits to the occupied lanes.  Bit-exact, and the
payoff of one-spike codes (TTFS) on narrow value distributions.

Fused epilogue (DESIGN.md §2): passing ``bias``/``mult`` runs the paper's
output logic (bias + ``layers.q_requantize`` multiply + clamp to
``[0, out_level]``, then the schedule's level-grid projection —
``out_grid="pow2"`` re-times TTFS's single output spike in-kernel) on the
int32 register tile before the store, emitting packed uint8 levels — the
raw accumulator never reaches HBM.  Without ``mult`` the kernel emits
int32 accumulators (logits-layer path).

Grid: (batch, C_out blocks, output-row tiles[, plane]).  VALID convs
(ops.py pre-pads SAME).  Each step reads an element-indexed band of
``(bh - 1) * stride + kh`` input rows starting at ``r * bh * stride``, so
neighbouring bands overlap by the halo and VMEM holds one band, never the
whole image: :func:`row_tile` keeps a tile's matmul rows (``bh * w_out``)
at or under ``TILE_ROWS``.  That is what fits VGG-11's 224x224 layers
(conv1 226x226x3, conv2 114x114x64) on a v5e.
"""

from __future__ import annotations

import functools
from typing import Literal, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.radix_matmul import (
    OCC_LANES,
    _project_levels,
    gated,
    mxu_dot,
    occ_mask,
)

__all__ = ["TILE_ROWS", "row_tile", "radix_conv2d_pallas"]

TILE_ROWS = 2048
"""Upper bound on one grid step's matmul rows (``bh * w_out``): the int32
accumulator tile is at most ``TILE_ROWS x bco x 4`` bytes of VMEM."""


def row_tile(h_out: int, w_out: int) -> int:
    """Output rows per grid step: the largest divisor of ``h_out`` whose
    tile stays within ``TILE_ROWS`` matmul rows (at least one row)."""
    best = 1
    for bh in range(1, h_out + 1):
        if h_out % bh == 0 and bh * w_out <= TILE_ROWS:
            best = bh
    return best


def _taps(x, w_ref, bh, w_out, *, kh, kw, stride, mxu_dtype, a_bits=None):
    """Strided VALID conv of one (rows, W, Cin) band -> (bh*w_out, bco).

    The (kh, kw) loops mirror the adder-array row/column iteration; each
    tap is an MXU matmul over Cin (the FPGA's sequential input-channel
    loop, parallelized on the MXU's contraction dim)."""
    cin = x.shape[-1]
    acc = None
    for r in range(kh):
        for c in range(kw):
            # rows/cols on the stride grid only — no discarded outputs
            window = x[r:r + (bh - 1) * stride + 1:stride,
                       c:c + (w_out - 1) * stride + 1:stride, :]
            part = mxu_dot(window.reshape(bh * w_out, cin), w_ref[r, c],
                           mxu_dtype, a_bits=a_bits)
            acc = part if acc is None else acc + part
    return acc


def _conv_acc(x, taps, zero, *, num_steps, method, periods, occ):
    """All plane passes of one band in one grid step.

    ``periods > 1`` (phase coding, bitserial only) replays the plane
    passes with the tiled per-phase weight schedule and divides back down
    — exact, the sum being ``periods ×`` the single-period value.  ``occ``
    gates each bitserial plane's tap sweep behind a ``lax.cond`` (empty
    plane -> no MXU work) and masks the fused pass's packed bits."""
    if method == "fused":
        if occ is not None:
            x = x & occ_mask(occ, num_steps)  # masked pass: occupied bits
        return taps(x, a_bits=num_steps)      # radix identity: one pass

    def plane_conv(shift):
        # dynamic early-exit: the whole tap sweep runs only when occupied
        return gated(occ, shift, lambda: taps((x >> shift) & 1), zero)

    acc = zero
    if periods == 1:
        for t in range(num_steps):            # paper-faithful Horner loop
            acc = (acc << 1) + plane_conv(num_steps - 1 - t)
        return acc
    for t in range(num_steps * periods):      # phase: tiled weight schedule
        shift = num_steps - 1 - (t % num_steps)
        acc = acc + (plane_conv(shift) << shift)
    return acc // periods


def _conv_kernel(*refs, num_steps, method, kh, kw, stride, periods,
                 out_level, out_grid, mxu_dtype, sparse, epilogue,
                 plane_parallel):
    """One (image, C_out block, row tile[, plane]) grid step.

    Refs, in order: x band, weights, [occupancy], [bias, mult], out,
    [accumulator scratch].  Plane-parallel steps (bitserial only) run ONE
    plane pass each — the plane index is the innermost grid dimension and
    the weight block's index map ignores it, so the weight tile stays
    VMEM-resident across all ``T x periods`` passes (weight-stationary);
    the Horner chain is reassociated into ``(plane_t conv w) << shift_t``
    terms, exact in int32.  The int32 sum then lives in the output block
    (raw path) or the scratch tile (epilogue path) across plane steps."""
    refs = list(refs)
    x_ref, w_ref = refs.pop(0), refs.pop(0)
    occ = refs.pop(0)[0] if sparse else None
    bias_ref, mult_ref = (refs.pop(0), refs.pop(0)) if epilogue else (
        None, None)
    o_ref = refs.pop(0)
    acc_ref = refs.pop(0) if refs else None
    _, bh, w_out, bco = o_ref.shape
    x = x_ref[0].astype(jnp.int32)            # (rows, W, Cin) band
    zero = jnp.zeros((bh * w_out, bco), jnp.int32)
    taps = functools.partial(_taps, w_ref=w_ref, bh=bh, w_out=w_out, kh=kh,
                             kw=kw, stride=stride, mxu_dtype=mxu_dtype)

    def store(acc):
        if not epilogue:
            o_ref[0] = acc.reshape(bh, w_out, bco)
            return
        # the paper's output logic on the int32 tile: identical float ops
        # to layers.q_requantize -> bit-exact twin
        q = jnp.floor((acc + bias_ref[...]).astype(jnp.float32)
                      * mult_ref[...])
        # reshape while 32-bit: Mosaic cannot regroup the rows of a uint8
        # tile narrower than 128 lanes unless w_out is 4-row aligned
        o_ref[0] = _project_levels(q.reshape(bh, w_out, bco),
                                   out_level=out_level, out_grid=out_grid)

    if not plane_parallel:
        store(_conv_acc(x, taps, zero, num_steps=num_steps, method=method,
                        periods=periods, occ=occ))
        return

    t_idx = pl.program_id(3)
    last = t_idx == num_steps * periods - 1
    shift = num_steps - 1 - jax.lax.rem(t_idx, num_steps)
    contrib = gated(occ, shift, lambda: taps((x >> shift) & 1) << shift,
                    zero)
    if epilogue:
        @pl.when(t_idx == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += contrib

        @pl.when(last)
        def _epilogue():
            store(acc_ref[...] // periods if periods > 1 else acc_ref[...])
        return

    @pl.when(t_idx == 0)
    def _init_out():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[0] = o_ref[0] + contrib.reshape(bh, w_out, bco)
    if periods > 1:
        @pl.when(last)
        def _div():
            o_ref[...] = o_ref[...] // periods


@functools.partial(
    jax.jit,
    static_argnames=("num_steps", "method", "bco", "stride", "interpret",
                     "out_steps", "periods", "out_level", "out_grid",
                     "mxu_dtype", "plane_parallel"))
def radix_conv2d_pallas(
    x_q: jax.Array,
    w_q: jax.Array,
    *,
    num_steps: int,
    method: Literal["bitserial", "fused"] = "bitserial",
    bco: int = 128,
    stride: int = 1,
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
    """(N, H, W, Cin) uint8 @ (KH, KW, Cin, Cout) int8 -> VALID conv.

    Without ``mult``: int32 accumulators.  With ``mult`` (f32 ``(1, Cout)``)
    and optional ``bias`` (int32 ``(1, Cout)``): fused output-logic epilogue,
    packed uint8 levels out, clamped to ``[0, out_level]`` and projected
    onto ``out_grid`` ("dense" clip, or "pow2" for TTFS's log-spaced
    re-timing); ``out_level`` defaults to ``2^out_steps - 1`` with
    ``out_steps`` defaulting to ``num_steps`` (they differ when inputs
    carry extra integer bits, e.g. after a sum-pool).  ``periods`` (phase
    coding, bitserial only) replays the plane schedule with tiled
    per-phase weights and an exact in-kernel divide.  ``occupancy``
    (``(1, OCC_LANES)`` int32 from ``ops.plane_occupancy``) turns on the
    sparsity-aware schedule (empty planes skipped/masked, bit-exact).
    ``mxu_dtype`` selects the per-plane dot lowering (see
    ``radix_matmul.mxu_dot``); ``plane_parallel`` (bitserial only) moves
    the plane loop into the innermost grid dimension under
    weight-stationary specs.  Cout must be a multiple of ``bco`` (ops.py
    pads); ``stride`` subsamples inside the kernel."""
    n, h, w, cin = x_q.shape
    kh, kw, cin2, cout = w_q.shape
    assert cin == cin2, (x_q.shape, w_q.shape)
    assert cout % bco == 0, (cout, bco)
    if plane_parallel and method != "bitserial":
        raise ValueError("plane_parallel requires method='bitserial' "
                         "(the fused dataflow has a single pass)")
    h_out = (h - kh) // stride + 1
    w_out = (w - kw) // stride + 1
    bh = row_tile(h_out, w_out)
    rows_in = (bh - 1) * stride + kh

    grid = (n, cout // bco, h_out // bh)
    if plane_parallel:
        grid += (num_steps * periods,)

    def spec(block, index):
        # the plane index (grid dim 3, when present) addresses no block
        return pl.BlockSpec(block, lambda b, co, r, *t: index(b, co, r))

    specs = [
        # element-indexed band: the index map returns element offsets,
        # so consecutive bands overlap by the kh - stride halo rows
        spec(tuple(pl.Element(d) for d in (1, rows_in, w, cin)),
             lambda b, co, r: (b, r * bh * stride, 0, 0)),
        spec((kh, kw, cin, bco), lambda b, co, r: (0, 0, 0, co)),
    ]
    args = [x_q, w_q]
    sparse = occupancy is not None
    if sparse:
        assert occupancy.shape == (1, OCC_LANES), occupancy.shape
        specs.append(spec((1, OCC_LANES), lambda b, co, r: (0, 0)))
        args.append(occupancy.astype(jnp.int32))
    epilogue = mult is not None
    scratch = []
    if epilogue:
        out_steps = num_steps if out_steps is None else out_steps
        out_level = (1 << out_steps) - 1 if out_level is None else out_level
        assert out_level <= 255, (
            "packed uint8 epilogue requires out_level <= 255")
        if bias is None:
            bias = jnp.zeros((1, cout), jnp.int32)
        assert bias.shape == (1, cout) and mult.shape == (1, cout), (
            bias.shape, mult.shape)
        row = spec((1, bco), lambda b, co, r: (0, co))
        specs += [row, row]
        args += [bias, mult.astype(jnp.float32)]
        if plane_parallel:
            # the accumulator must survive across plane grid steps
            scratch = [pltpu.VMEM((bh * w_out, bco), jnp.int32)]

    kernel = functools.partial(
        _conv_kernel, num_steps=num_steps, method=method, kh=kh, kw=kw,
        stride=stride, periods=periods, out_level=out_level,
        out_grid=out_grid, mxu_dtype=mxu_dtype, sparse=sparse,
        epilogue=epilogue, plane_parallel=plane_parallel)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=specs,
        out_specs=spec((1, bh, w_out, bco), lambda b, co, r: (b, r, 0, co)),
        out_shape=jax.ShapeDtypeStruct((n, h_out, w_out, cout),
                                       jnp.uint8 if epilogue else jnp.int32),
        scratch_shapes=scratch,
        interpret=interpret,
    )(*args)
