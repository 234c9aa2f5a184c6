"""Plain reference forward of a radix-quantized CNN, independent of the program.

Written from the configuration file alone (``bench/configs/<name>.json``):
the same integer semantics the served path promises, in straightforward
``jax.numpy`` with no kernels, padding, bucketing or caches.

* input:  ``q = clip(floor(x * 2^T), 0, 2^T - 1)`` (images in [0, 1))
* conv / linear: integer accumulator ``sum(q * w) + b`` (exact)
* requantize: ``clip(floor(acc * mult), 0, 2^T - 1)`` in float32
* or-pool: bitwise OR of the levels over each window
* last layer: float logits ``acc * logit_scale``

Weights come from :mod:`netgen` (made by the benchmark from the seed),
never from the program.  In float32 every accumulator is an integer below
2^24 (levels <= 15, weights <= 3, fan-in <= 25088), so the convolutions
at ``precision=HIGHEST`` are exact.

``dtype=jnp.bfloat16`` is the control: the same arithmetic one precision
step below what the configuration states, which the comparison must
reject.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def levels(cfg: dict) -> int:
    return 2 ** int(cfg["num_steps"])


def quantize_input(cfg: dict, x, dtype=jnp.float32):
    top = levels(cfg) - 1
    q = jnp.floor(x.astype(dtype) * jnp.asarray(levels(cfg), dtype))
    return jnp.clip(q, 0, top)


def affine(layer: dict, q, w, dtype=jnp.float32):
    """Integer accumulator of one conv or linear layer (bias excluded)."""
    if layer["kind"] == "conv":
        return jax.lax.conv_general_dilated(
            q.astype(dtype), w.astype(dtype),
            window_strides=(layer["stride"],) * 2,
            padding=layer["padding"],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=HIGHEST, preferred_element_type=dtype)
    return jnp.dot(q.astype(dtype), w.astype(dtype), precision=HIGHEST,
                   preferred_element_type=dtype)


def requantize(cfg: dict, acc, mult, dtype=jnp.float32):
    top = levels(cfg) - 1
    return jnp.clip(jnp.floor(acc.astype(dtype) * mult.astype(dtype)), 0, top)


def or_pool(q, window: int):
    n, h, w, c = q.shape
    h2, w2 = h // window, w // window
    v = q[:, :h2 * window, :w2 * window].astype(jnp.int32)
    v = v.reshape(n, h2, window, w2, window, c)
    out = v[:, :, 0, :, 0]
    for i in range(window):
        for j in range(window):
            out = out | v[:, :, i, :, j]
    return out.astype(q.dtype)


def forward(cfg: dict, weights: dict, x, *, dtype=jnp.float32):
    """(n, H, W, C) float images -> (n, classes) float32 logits."""
    q = quantize_input(cfg, x, dtype)
    params = weights["layers"]
    for layer, p in zip(cfg["layers"], params):
        kind = layer["kind"]
        if kind in ("conv", "linear"):
            acc = affine(layer, q, p["w"], dtype) + p["b"].astype(dtype)
            if p["mult"] is None:
                return (acc.astype(dtype)
                        * weights["logit_scale"].astype(dtype)
                        ).astype(jnp.float32)
            q = requantize(cfg, acc, p["mult"], dtype)
        elif kind == "pool":
            if layer["mode"] != "or":
                raise ValueError(f"pool mode {layer['mode']!r}")
            q = or_pool(q, layer["window"])
        elif kind == "flatten":
            q = q.reshape(q.shape[0], -1)
        else:
            raise ValueError(kind)
    raise ValueError("configuration has no final affine layer")


def make_forward(cfg: dict, *, dtype=jnp.float32):
    """Jitted ``forward(weights, x)`` for one configuration."""
    return jax.jit(lambda weights, x: forward(cfg, weights, x, dtype=dtype))
