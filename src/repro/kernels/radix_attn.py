"""Pallas blockwise decode attention over the packed radix KV cache.

PR 9 left decode attention as the last dense-float island: the radix KV
cache stores K/V as T-bit levels + per-(token, head) scales, but
``lm/blocks.decode_attention`` dequantized the whole cache to float before
the softmax.  This kernel consumes the packed cache directly:

* **Plane-weight QK^T.**  The decode query is radix-quantized on the fly
  (``quantize_q``: the same affine-shift scheme as the matmul activations,
  at ``Q_BITS = 7`` so levels fit int8), making the score contraction an
  integer x integer dot.  With ``a = 2 qq / qlvl - 1`` and
  ``b = 2 qk / lvl - 1`` the dequantized dot expands exactly:

      sum_d q_d k_d = qs * sk * [ 4/(qlvl*lvl) * <qq, qk>
                                  - 2/qlvl * sum(qq) - 2/lvl * sum(qk) + hd ]

  so ONE integer dot per (query-group, KV-block) tile plus rank-1
  corrections replaces the dequantize — and the integer dot runs either as
  the fused packed pass or bit-serially over K's spike planes, each plane
  pass gated behind the PR-5 ``plane_occupancy`` prepass (an empty plane
  never hits the MXU) and lowered per ``mxu_dtype`` under the same
  ``autotune.exact_lowering`` guard as the matmul kernels (int8 is exact
  here because ``qq <= 127`` by construction and plane bits are 0/1).

* **Scale-folded streaming softmax.**  Scores fold the per-token k-scale
  before the running-max update; the probability row folds the per-token
  v-scale (``pw = p * sv``), so the value sum is again plane algebra:

      sum_j p_j v_j = 2/lvl * (pw @ qv) - sum_j pw_j

  The online-softmax state (running max ``m``, renormalized sum ``l``,
  output accumulator) lives in VMEM scratch across the KV-block grid —
  only one (group, block) score tile is ever live, and the full
  dequantized (B, S, Hkv, hd) float K/V never materializes anywhere.

* **Nibble unpack in VMEM.**  When the cache is byte-packed (two T<=4
  levels per byte), each KV block unpacks hi/lo nibbles *inside* the
  kernel via a layout-friendly concat: the wrapper permutes the query's
  head-dim columns to ``[even dims | odd dims]`` once, so the unpacked
  block is ``concat(hi, lo)`` instead of an interleave, and the output
  columns are inverse-permuted on the way out.  Exact — the contraction
  is permutation-invariant and the algebra's rank-1 terms only see sums.

Masks arrive as a per-(batch, slot) boolean (full causal or the sliding
-window ring-buffer validity from ``blocks.decode_mask``); masked slots
score ``-1e30`` and their probabilities are hard-zeroed, so an all-masked
block cannot NaN the stream (``osm_update``).

The integer QK part is bit-exact across lowerings and block sizes; the
float softmax/value part reassociates across block partitions, so
strategies agree to f32 rounding (~1e-6 relative) rather than bit-for-bit
— the differential suite (tests/test_attn_differential.py) pins every
path to the ``kernels/ref.py`` plane-level oracle.

Grid: (B * Hkv, S / blk), KV-block dim innermost ("arbitrary" semantics)
so the scratch state streams over the cache exactly once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.radix_matmul import (
    OCC_LANES,
    gated,
    int8_contract,
    occ_mask,
)

__all__ = [
    "Q_BITS",
    "MASKED",
    "quantize_q",
    "plane_scores",
    "osm_init",
    "osm_update",
    "osm_finalize",
    "radix_decode_attn_kernel",
    "radix_decode_attn_pallas",
]

Q_BITS = 7
"""Decode-query quantization bits: 2^7 - 1 = 127 levels — the int8
ceiling, so the QK^T integer dot is MXU int8-eligible for every cache T,
and the added query error (~1/254 of the row range) stays far below the
T<=8 KV dequantization error the cache already carries."""

MASKED = -1e30
"""Masked-score fill value — finite (not -inf) so the running max is
always well-defined and an all-masked block yields exp(0) rescales with
hard-zeroed probabilities instead of NaN."""


def quantize_q(q: jax.Array, q_bits: int = Q_BITS):
    """Signed query -> (int32 radix levels, per-row scale).

    The same affine shift as ``lm/radix._radix_activation`` (u = (x/s+1)/2
    against the per-row absmax), kept int32 so the kernel's plane dots can
    lower the operand per ``mxu_dtype`` without re-rounding."""
    qlvl = (1 << q_bits) - 1
    s = jnp.max(jnp.abs(q), axis=-1, keepdims=True).astype(jnp.float32) + 1e-9
    u = (q.astype(jnp.float32) / s + 1.0) * 0.5
    lv = jnp.clip(jnp.round(u * qlvl), 0, qlvl).astype(jnp.int32)
    return lv, s


def plane_scores(sint, qsum, ksum, qs, sk, *, hd: int, num_steps: int,
                 q_bits: int) -> jax.Array:
    """Fold the affine shifts + per-token scales out of the integer dot.

    ``sint`` (..., g, blk) int32 = <qq, qk> contractions; ``qsum`` the
    query level row-sums (..., g, 1); ``ksum`` the key level sums
    broadcastable over (..., g, blk); ``qs`` the query scales (..., g, 1);
    ``sk`` the key scales broadcastable over (..., g, blk).  ``hd`` is the
    TRUE head dim (zero-padded columns contribute 0 to every sum, so the
    ``+ hd`` constant must count real dims only).  Includes the
    ``hd**-0.5`` attention scale."""
    lvl = (1 << num_steps) - 1
    qlvl = (1 << q_bits) - 1
    raw = ((4.0 / (qlvl * lvl)) * sint.astype(jnp.float32)
           - (2.0 / qlvl) * qsum.astype(jnp.float32)
           - (2.0 / lvl) * ksum.astype(jnp.float32)
           + float(hd))
    return (hd ** -0.5) * qs * sk * raw


# ---------------------------------------------------------------------------
# Online-softmax core: pure functions shared by the Pallas kernel, the XLA
# twin, and the property tests (block-split invariance, all-masked
# stability, scale-fold associativity — tests/test_attn_differential.py).
# ---------------------------------------------------------------------------


def osm_init(shape_gl, shape_o):
    """Zero streaming state: (m, l, o) with m at the MASKED floor."""
    return (jnp.full(shape_gl, MASKED, jnp.float32),
            jnp.zeros(shape_gl, jnp.float32),
            jnp.zeros(shape_o, jnp.float32))


def osm_update(state, scores, mask, pv):
    """One streaming softmax block update.

    ``scores`` (..., g, blk) f32 raw (pre-mask) scores; ``mask`` boolean,
    broadcastable over scores (False = excluded); ``pv`` a callable
    mapping the un-normalized probability tile ``p`` (same shape as
    scores) to the value contribution (..., g, hd) — callers fold the
    per-token v-scales inside it.  Masked entries are hard-zeroed in
    ``p`` (not just exp(-1e30)): when the running max itself sits at the
    MASKED floor, exp(score - m) would be exp(0) = 1 for masked slots.
    """
    m, l, o = state
    s = jnp.where(mask, scores, MASKED)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    o_new = o * alpha + pv(p)
    return (m_new, l_new, o_new)


def osm_finalize(state):
    """o / l with an exact all-masked guard: l > 0 whenever any slot was
    valid (the max element contributes exp(0) = 1), so dividing by
    max(l, 1) only differs on fully-masked rows — which return 0, not
    NaN."""
    m, l, o = state
    return o / jnp.where(l > 0, l, 1.0)


# ---------------------------------------------------------------------------
# In-kernel helpers.
# ---------------------------------------------------------------------------


def _dot_nt(a, b, mxu_dtype: str, *, a_bits=None, b_bits=None) -> jax.Array:
    """(g, d) x (blk, d) -> (g, blk) int32, contracting the shared last
    dim — ``mxu_dot``'s lowering contract for the transposed-operand
    layout attention uses (K arrives token-major).  ``a_bits``/``b_bits``
    bound the non-negative level operands for the int8 lowering."""
    dn = (((1,), (1,)), ((), ()))
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


def _dot_nt_f32(a, b) -> jax.Array:
    """(g, blk) f32 x (hd, blk)^T layout -> contract blk: (g, hd) f32."""
    return jax.lax.dot_general(
        a.astype(jnp.float32), b.astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def unpack_levels(x, packed: bool) -> jax.Array:
    """uint8 block -> int32 levels.  Packed blocks (two T<=4 levels per
    byte) unpack as ``concat(hi, lo)`` along the head dim — the wrapper
    permutes query columns to the matching ``[even | odd]`` order, which
    keeps the unpack a lane-friendly concat instead of an interleave."""
    xi = x.astype(jnp.int32)
    if not packed:
        return xi
    return jnp.concatenate([(xi >> 4) & 0xF, xi & 0xF], axis=-1)


def _qk_tile(qq, kq, occ, *, num_steps: int, q_bits: int, method: str,
             mxu_dtype: str) -> jax.Array:
    """<qq, qk> integer tile: fused single pass over packed levels, or
    bit-serial plane passes — each gated behind the occupancy prepass so
    globally-empty spike planes never reach the MXU.  Exact either way
    (an empty plane contributes zero; masking occupied-only bits is the
    identity on real data)."""
    if method == "fused":
        kq_m = kq if occ is None else kq & occ_mask(occ, num_steps)
        return _dot_nt(qq, kq_m, mxu_dtype, a_bits=q_bits, b_bits=num_steps)
    zero = jnp.zeros((qq.shape[0], kq.shape[0]), jnp.int32)
    sint = zero
    for s in range(num_steps):
        plane = (kq >> s) & 1
        sint = sint + (gated(
            occ, s,
            lambda plane=plane: _dot_nt(qq, plane, mxu_dtype, a_bits=q_bits),
            zero) << s)
    return sint


def _pv_tile(pw, vq, occ, *, num_steps: int, method: str) -> jax.Array:
    """(g, blk) scale-folded probabilities x (blk, hd) value levels ->
    (g, hd) f32 — same plane schedule and occupancy gating as QK^T, but
    the probability operand is genuinely float so every pass runs f32
    (exact to f32 rounding; plane bits are exact float carriers)."""
    if method == "fused":
        vq_m = vq if occ is None else vq & occ_mask(occ, num_steps)
        return _dot_nt_f32(pw, vq_m)
    zero = jnp.zeros((pw.shape[0], vq.shape[1]), jnp.float32)
    acc = zero
    for s in range(num_steps):
        plane = (vq >> s) & 1
        acc = acc + gated(
            occ, s, lambda plane=plane: _dot_nt_f32(pw, plane),
            zero) * float(1 << s)
    return acc


# ---------------------------------------------------------------------------
# The Pallas kernel.
# ---------------------------------------------------------------------------


def radix_decode_attn_kernel(
    qq_ref, qs_ref, kq_ref, ks_ref, vq_ref, vs_ref, mask_ref,
    occk_ref, occv_ref, o_ref, m_ref, l_ref, acc_ref,
    *, num_steps: int, q_bits: int, hd: int, method: str, packed: bool,
    mxu_dtype: str, sparsity: bool,
):
    """One (kv-head row, KV block) step of the streaming decode attention.

    Grid dim 0 walks the B*Hkv rows, dim 1 the KV blocks (innermost, so
    the (m, l, acc) VMEM scratch carries the online-softmax state across
    the whole cache for one row).  Block shapes: qq (1, g, hd) int32
    levels, qs (1, g, 1), kq/vq (1, blk, hd or hd//2) uint8, ks/vs/mask
    (1, 1, blk), occ (1, OCC_LANES) — the last two dims of every block
    are lane/sublane aligned or span the array, as the TPU requires."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qq = qq_ref[0]                                     # (g, hd) int32
    qs = qs_ref[0]                                     # (g, 1) f32
    kq = unpack_levels(kq_ref[0], packed)              # (blk, hd) int32
    vq = unpack_levels(vq_ref[0], packed)
    sk = ks_ref[0]                                     # (1, blk) f32
    sv = vs_ref[0]
    mask = mask_ref[0] > 0                             # (1, blk) bool
    occk = occk_ref[0] if sparsity else None
    occv = occv_ref[0] if sparsity else None

    sint = _qk_tile(qq, kq, occk, num_steps=num_steps, q_bits=q_bits,
                    method=method, mxu_dtype=mxu_dtype)
    qsum = jnp.sum(qq, axis=-1, keepdims=True)         # (g, 1) int32
    ksum = jnp.sum(kq, axis=-1)[None, :]               # (1, blk) int32
    scores = plane_scores(sint, qsum, ksum, qs, sk, hd=hd,
                          num_steps=num_steps, q_bits=q_bits)

    lvl = (1 << num_steps) - 1

    def pv(p):
        pw = p * sv                                    # fold v scales
        vint = _pv_tile(pw, vq, occv, num_steps=num_steps, method=method)
        return (2.0 / lvl) * vint - jnp.sum(pw, axis=-1, keepdims=True)

    state = osm_update((m_ref[...], l_ref[...], acc_ref[...]),
                       scores, mask, pv)
    m_ref[...], l_ref[...], acc_ref[...] = state

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[0] = osm_finalize((m_ref[...], l_ref[...], acc_ref[...]))


@functools.partial(
    jax.jit,
    static_argnames=("num_steps", "q_bits", "hd", "method", "packed",
                     "blk", "mxu_dtype", "sparsity", "interpret"))
def radix_decode_attn_pallas(
    qq: jax.Array,
    qs: jax.Array,
    kq: jax.Array,
    ks: jax.Array,
    vq: jax.Array,
    vs: jax.Array,
    mask: jax.Array,
    occ_k: jax.Array,
    occ_v: jax.Array,
    *,
    num_steps: int,
    q_bits: int = Q_BITS,
    hd: int,
    method: str = "bitserial",
    packed: bool = False,
    blk: int = 128,
    mxu_dtype: str = "int8",
    sparsity: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """Blockwise packed decode attention, (N = B*Hkv)-row layout.

    qq (N, g, hd) int32 query levels (columns pre-permuted to
    ``[even | odd]`` when ``packed``), qs (N, g) f32 query scales,
    kq/vq (N, S, hd or hd//2) uint8 cache levels, ks/vs (N, S) f32
    per-token scales, mask (N, S) int32 (1 = attend), occ_k/occ_v
    (1, OCC_LANES) int32 plane-occupancy rows.  Returns (N, g, hd) f32
    attention outputs (columns still permuted when ``packed`` — the
    ops.py wrapper inverse-permutes).  ``S`` must be a multiple of
    ``blk`` (ops.py pads; padded slots carry mask 0)."""
    n, g, hdq = qq.shape
    s_len = kq.shape[1]
    assert s_len % blk == 0, (s_len, blk)
    assert occ_k.shape == (1, OCC_LANES), occ_k.shape
    nj = s_len // blk
    hdp = kq.shape[2]

    assert hdq == (2 * hdp if packed else hdp), (hdq, hdp, packed)

    kernel = functools.partial(
        radix_decode_attn_kernel, num_steps=num_steps, q_bits=q_bits,
        hd=hd, method=method, packed=packed, mxu_dtype=mxu_dtype,
        sparsity=sparsity)
    return pl.pallas_call(
        kernel,
        grid=(n, nj),
        in_specs=[
            pl.BlockSpec((1, g, hdq), lambda n_, j_: (n_, 0, 0)),      # qq
            pl.BlockSpec((1, g, 1), lambda n_, j_: (n_, 0, 0)),        # qs
            pl.BlockSpec((1, blk, hdp), lambda n_, j_: (n_, j_, 0)),   # kq
            pl.BlockSpec((1, 1, blk), lambda n_, j_: (n_, 0, j_)),     # ks
            pl.BlockSpec((1, blk, hdp), lambda n_, j_: (n_, j_, 0)),   # vq
            pl.BlockSpec((1, 1, blk), lambda n_, j_: (n_, 0, j_)),     # vs
            pl.BlockSpec((1, 1, blk), lambda n_, j_: (n_, 0, j_)),     # mask
            pl.BlockSpec((1, OCC_LANES), lambda n_, j_: (0, 0)),       # occ_k
            pl.BlockSpec((1, OCC_LANES), lambda n_, j_: (0, 0)),       # occ_v
        ],
        out_specs=pl.BlockSpec((1, g, hdq), lambda n_, j_: (n_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, g, hdq), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),      # running max m
            pltpu.VMEM((g, 1), jnp.float32),      # renormalized sum l
            pltpu.VMEM((g, hdq), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
    )(qq, qs[:, :, None], kq.astype(jnp.uint8), ks[:, None, :],
      vq.astype(jnp.uint8), vs[:, None, :],
      mask.astype(jnp.int32)[:, None, :], occ_k.astype(jnp.int32),
      occ_v.astype(jnp.int32))
