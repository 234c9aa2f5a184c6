"""Spiking / quantized layer functional forms.

Every layer exists as a *twin pair* sharing the same integer arithmetic:

* ``q_*``   — the quantized-ANN form: packed integer activations
              (uint8 levels in [0, 2^T - 1]).
* ``snn_*`` — the paper-faithful spiking form: radix spike trains
              (T, ...) in {0,1}, Horner-accumulated over time steps.

The pair is bit-exact by construction (property-tested): the spiking form
computes ``sum_t 2^(T-1-t) * linop(plane_t, W)`` which equals
``linop(packed, W)`` by linearity.  This is the algebraic heart of the paper
and the reason radix encoding admits a single-pass TPU execution (see
kernels/ and DESIGN.md §2).

Data layout: NHWC for 2-D activations, HWIO for conv kernels (TPU native).
Spike trains put time first: (T, N, H, W, C) / (T, N, F).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import encoding, neuron

__all__ = [
    "q_conv2d",
    "snn_conv2d",
    "q_linear",
    "snn_linear",
    "q_avg_pool",
    "snn_avg_pool",
    "q_max_pool",
    "snn_max_pool",
    "q_or_pool",
    "snn_or_pool",
    "q_requantize",
    "sum_pool_bits",
]

# integer conv/matmul helpers ------------------------------------------------


def _int_conv(x: jax.Array, w: jax.Array, stride: int, padding: str | Tuple) -> jax.Array:
    """int8/uint8 conv with int32 accumulation (NHWC * HWIO -> NHWC)."""
    return lax.conv_general_dilated(
        x.astype(jnp.int32),
        w.astype(jnp.int32),
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.int32,
    )


def _int_matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    return lax.dot_general(
        x.astype(jnp.int32),
        w.astype(jnp.int32),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


def q_requantize(acc: jax.Array, num_steps: int, mult) -> jax.Array:
    """Shared ReLU+requantize stage (== neuron.radix_fire).

    This is the semantic contract of the kernels' fused output-logic
    epilogue (kernels/radix_matmul.py, kernels/radix_conv.py): the in-kernel
    bias+multiply+clamp must be bit-exact against ``q_requantize(acc +
    b_int, T, mult)`` — tests/test_fused_epilogue.py sweeps it.
    """
    return neuron.radix_fire(acc, num_steps, mult)


def sum_pool_bits(bits: int, window: int) -> int:
    """Integer bits carried by a sum-pool output whose inputs use ``bits``.

    The paper's pooling unit has no output requantizer, so an avg (sum) pool
    widens activations from T to ``sum_pool_bits(T, window)`` bits until the
    next layer's multiplier folds the window division back in (DESIGN.md
    §2); the engine's plan compilation uses this to decide whether the
    carry still fits the packed byte format.
    """
    return max(1, int(((1 << bits) - 1) * window * window).bit_length())


# convolution ----------------------------------------------------------------


def q_conv2d(
    q_in: jax.Array,
    w_q: jax.Array,
    b_int: jax.Array,
    *,
    stride: int = 1,
    padding: str = "VALID",
) -> jax.Array:
    """Integer conv accumulator (no requant): (N,H,W,Cin) u8 -> (N,H',W',Cout) i32."""
    return _int_conv(q_in, w_q, stride, padding) + b_int


def snn_conv2d(
    planes: jax.Array,
    w_q: jax.Array,
    b_int: jax.Array,
    *,
    stride: int = 1,
    padding: str = "VALID",
) -> jax.Array:
    """Radix spike-train conv: Horner over T binary-plane convs (paper Alg. 1).

    planes: (T, N, H, W, Cin) in {0,1}.  Returns int32 accumulator
    (N, H', W', Cout) — identical to ``q_conv2d(pack(planes), ...)``.
    """
    per_step = jax.vmap(lambda p: _int_conv(p, w_q, stride, padding))(planes)
    return neuron.radix_membrane(per_step) + b_int


# linear ---------------------------------------------------------------------


def q_linear(q_in: jax.Array, w_q: jax.Array, b_int: jax.Array) -> jax.Array:
    """Integer matmul accumulator: (N,F) u8 @ (F,G) i8 -> (N,G) i32."""
    return _int_matmul(q_in, w_q) + b_int


def snn_linear(planes: jax.Array, w_q: jax.Array, b_int: jax.Array) -> jax.Array:
    """Radix spike-train linear layer (Horner over per-plane matmuls)."""
    per_step = jax.vmap(lambda p: _int_matmul(p, w_q))(planes)
    return neuron.radix_membrane(per_step) + b_int


# pooling --------------------------------------------------------------------


def _pool_window(window: int, stride=None, padding="VALID"):
    """``reduce_window`` dimensions, strides and padding of an NHWC pool:
    stride defaults to the window; ``padding`` is "VALID" or explicit
    ``((lo, hi), (lo, hi))`` zero pads of H and W."""
    s = window if stride is None else stride
    if not isinstance(padding, str):
        (a, b), (c, d) = padding
        padding = ((0, 0), (a, b), (c, d), (0, 0))
    return (1, window, window, 1), (1, s, s, 1), padding


def q_avg_pool(q_in: jax.Array, window: int, stride=None,
               padding="VALID") -> jax.Array:
    """Sum-pool accumulator (int32).  The window-size division is folded into
    the next layer's requant multiplier, as hardware would."""
    return lax.reduce_window(
        q_in.astype(jnp.int32),
        jnp.int32(0),
        lax.add,
        *_pool_window(window, stride, padding),
    )


def snn_avg_pool(planes: jax.Array, window: int, stride=None,
                 padding="VALID") -> jax.Array:
    """Spiking sum-pool: per-plane window sums, Horner over time."""
    per_step = jax.vmap(
        lambda p: q_avg_pool(p, window, stride, padding))(planes)
    return neuron.radix_membrane(per_step)


def q_max_pool(q_in: jax.Array, window: int, stride=None,
               padding="VALID") -> jax.Array:
    """Max pool of packed levels.  Zero padding equals -inf padding here:
    levels are non-negative and every window of a padding below the
    window size holds a real element."""
    return lax.reduce_window(
        q_in,
        jnp.zeros((), q_in.dtype),
        lax.max,
        *_pool_window(window, stride, padding),
    )


def q_or_pool(q_in: jax.Array, window: int, stride=None,
              padding="VALID") -> jax.Array:
    """Bitwise-OR pooling of packed radix levels.

    The paper's pooling unit has *no output logic* (no requantizer): it pools
    each time-step plane independently, i.e. an OR over the window per plane.
    On packed integers that is exactly a bitwise OR over the window — the
    radix-domain "soft max" (an upper bound on true max, exact when the window
    max dominates bitwise).
    """
    return lax.reduce_window(
        q_in.astype(jnp.int32),
        jnp.int32(0),
        lax.bitwise_or,
        *_pool_window(window, stride, padding),
    ).astype(q_in.dtype)


def snn_or_pool(planes: jax.Array, window: int, stride=None,
                padding="VALID") -> jax.Array:
    """Per-plane OR pooling (binary max) — the spiking twin of ``q_or_pool``.

    Returns pooled spike planes (T, N, H', W', C); no Horner/requant stage,
    matching the paper's pooling unit.
    """
    return jax.vmap(lambda p: q_max_pool(p, window, stride, padding))(planes)


def snn_max_pool(planes: jax.Array, window: int) -> jax.Array:
    """Max-pool directly in the radix (bit-plane) domain.

    Max of radix-encoded values is a *lexicographic bit-plane max*: walk
    planes MSB->LSB keeping a per-element "still in contention" mask; the
    output bit is the max over in-contention elements, and elements whose bit
    differs from the output bit drop out.  Non-overlapping windows only
    (stride == window), which is what the paper's pooling unit implements.

    Returns the pooled train as packed integer levels (same contract as
    ``q_max_pool`` on packed input) — property-tested equal to
    ``q_max_pool(pack(planes))``.
    """
    num_steps = planes.shape[0]
    # crop to the VALID region (matches reduce_window "VALID" semantics)
    hc = planes.shape[2] // window * window
    wc = planes.shape[3] // window * window
    planes = planes[:, :, :hc, :wc, :]
    contention = jnp.ones(planes.shape[1:], jnp.int8)
    out_bits = []
    for t in range(num_steps):
        gated = planes[t] * contention  # bits of dropped-out elems read as 0
        out_bit = q_max_pool(gated, window)  # (N, H', W', C) in {0,1}
        # broadcast the winning bit back onto each window element
        up = jnp.repeat(jnp.repeat(out_bit, window, axis=1), window, axis=2)
        up = up[:, : planes.shape[2], : planes.shape[3], :]
        # an element stays in contention iff it matched every output bit so far
        contention = contention * (gated == up).astype(jnp.int8)
        out_bits.append(out_bit)
    return neuron.radix_membrane(jnp.stack(out_bits)).astype(planes.dtype)
