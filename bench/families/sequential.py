"""The sequential family: a chain of ``conv``, ``pool`` (mode ``or``),
``flatten`` and ``linear`` layers, as the configuration's ``layers``
lists them (VGG-11, LeNet-5).

Weights (:func:`make_weights`), made on the device in one jitted call:
3-bit weight levels (int8), small int32 biases and float32
requantization multipliers.  Each multiplier maps the 99.9th percentile
of its layer's non-negative accumulator, on a few seeded calibration
images, to the top level, as the paper's conversion calibrates.  The
logit scale is the configuration's constant ``logit_scale``: the program
compiles it into its plans, so a scale drawn from the seed would
recompile them for every seed.  The same arrays go to the program
(:func:`program_net`) and to the reference (:func:`make_forward`).

The plain reference, written from the configuration file alone: the
same integer semantics the served path promises, in straightforward
``jax.numpy`` with no kernels, padding, bucketing or caches.

* input:  ``q = clip(floor(x * 2^T), 0, 2^T - 1)`` (images in [0, 1))
* conv / linear: integer accumulator ``sum(q * w) + b`` (exact)
* requantize: ``clip(floor(acc * mult), 0, 2^T - 1)`` in float32
* or-pool: bitwise OR of the levels over each window
* last layer: float logits ``acc * logit_scale``

In float32 every accumulator is an integer below 2^24 (levels <= 15,
weights <= 3, fan-in <= 25088), so the convolutions at
``precision=HIGHEST`` are exact.  ``dtype=jnp.bfloat16`` is the control:
the same arithmetic one precision step below what the configuration
states, which the comparison must reject.
"""

from __future__ import annotations

import math
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

import netgen
import work

HIGHEST = jax.lax.Precision.HIGHEST
KERNELS = {"conv": "radix_conv2d_pallas", "matmul": "radix_matmul_pallas"}
# the kernel each weighted layer kind runs on
KERNEL_OF = {"conv": "conv", "linear": "matmul"}


# ---------------------------------------------------------------------------
# The plain reference.
# ---------------------------------------------------------------------------


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


def make_forward(cfg: dict, dtype=jnp.float32):
    """Jitted ``forward(weights, x)`` for one configuration."""
    return jax.jit(lambda weights, x: forward(cfg, weights, x, dtype=dtype))


# ---------------------------------------------------------------------------
# Shapes and seeded weights.
# ---------------------------------------------------------------------------


def layer_shapes(cfg: dict):
    """Per layer: the weight shape of conv/linear layers, else None; and
    the output (H, W, C) or (F,) shape of every layer."""
    h, w, c = cfg["input_hw"]
    shape = (h, w, c)
    wshapes, outs = [], []
    for layer in cfg["layers"]:
        kind = layer["kind"]
        if kind == "conv":
            k, s = layer["kernel"], layer["stride"]
            h, w, c = shape
            wshapes.append((k, k, c, layer["out"]))
            if layer["padding"] == "SAME":
                h, w = -(-h // s), -(-w // s)
            else:
                h, w = (h - k) // s + 1, (w - k) // s + 1
            shape = (h, w, layer["out"])
        elif kind == "pool":
            h, w, c = shape
            shape = (h // layer["window"], w // layer["window"], c)
            wshapes.append(None)
        elif kind == "flatten":
            shape = (int(np.prod(shape)),)
            wshapes.append(None)
        elif kind == "linear":
            wshapes.append((shape[0], layer["out"]))
            shape = (layer["out"],)
        else:
            raise ValueError(kind)
        outs.append(shape)
    return wshapes, outs


def _make_weights(cfg: dict, key):
    top = levels(cfg) - 1
    qmax = 2 ** (int(cfg["weight_bits"]) - 1) - 1
    wshapes, _ = layer_shapes(cfg)
    n_affine = sum(s is not None for s in wshapes)
    kcal, key = jax.random.split(key)
    q = quantize_input(
        cfg, netgen.images(cfg, kcal, int(cfg["calib_images"])))
    out, seen = [], 0
    for layer, shape in zip(cfg["layers"], wshapes):
        kind = layer["kind"]
        if kind == "pool":
            q = or_pool(q, layer["window"])
            out.append(None)
            continue
        if kind == "flatten":
            q = q.reshape(q.shape[0], -1)
            out.append(None)
            continue
        seen += 1
        kw, kb, key = jax.random.split(key, 3)
        w = jax.random.randint(kw, shape, -qmax, qmax + 1, jnp.int8)
        acc = affine(layer, q, w)
        spread = jnp.maximum(jnp.std(acc), 1.0)
        b = jnp.round(jax.random.normal(kb, (shape[-1],)) * 0.1 * spread
                      ).astype(jnp.int32)
        acc = acc + b.astype(jnp.float32)
        if seen == n_affine:
            out.append({"w": w, "b": b, "mult": None})
            break
        hi = jnp.percentile(jnp.maximum(acc, 0.0), 99.9)
        mult = (jnp.float32(top + 1) / jnp.maximum(hi, 1.0)
                ).astype(jnp.float32)
        q = requantize(cfg, acc, mult)
        out.append({"w": w, "b": b, "mult": mult})
    return {"layers": out,
            "logit_scale": jnp.float32(cfg["logit_scale"])}


def make_weights(cfg: dict, key) -> dict:
    """Every layer's served integers, from ``key``, in one jitted call."""
    return jax.jit(lambda k: _make_weights(cfg, k))(key)


def program_net(cfg: dict, weights: dict):
    """The program's ``QuantizedNet`` over the benchmark's arrays."""
    from repro.core import conversion, encoding

    static = []
    for layer in cfg["layers"]:
        kind = layer["kind"]
        if kind == "conv":
            static.append(("conv", {"stride": layer["stride"],
                                    "padding": layer["padding"]}))
        elif kind == "pool":
            static.append(("pool", {"window": layer["window"],
                                    "mode": layer["mode"]}))
        else:
            static.append((kind, {}))
    qlayers = [None if p is None else
               {"w_q": p["w"], "b_int": p["b"], "mult": p["mult"]}
               for p in weights["layers"]]
    if cfg["encoding"] != "radix":
        raise ValueError(f"encoding {cfg['encoding']!r}")
    return conversion.QuantizedNet(
        static=tuple(static),
        num_steps=int(cfg["num_steps"]),
        weight_bits=int(cfg["weight_bits"]),
        qlayers=qlayers,
        input_scale=1.0,
        logit_scale=float(np.asarray(weights["logit_scale"])),
        encoding=encoding.RadixEncoding(int(cfg["num_steps"])),
    )


# ---------------------------------------------------------------------------
# Logical work.
# ---------------------------------------------------------------------------


def layers(cfg: dict) -> List[work.LayerWork]:
    """Per conv / linear layer of ``cfg``, in order."""
    wshapes, outs = layer_shapes(cfg)
    shape = tuple(cfg["input_hw"])
    n_affine = sum(s is not None for s in wshapes)
    out, seen = [], 0
    for layer, wshape, oshape in zip(cfg["layers"], wshapes, outs):
        if wshape is not None:
            seen += 1
            positions = math.prod(oshape[:-1])
            fan_in = math.prod(wshape[:-1])
            out_elem = 4 if seen == n_affine else 1
            out.append(work.LayerWork(
                name=f"{layer['kind']}{seen}",
                kernel=KERNEL_OF[layer["kind"]],
                macs=positions * fan_in * wshape[-1],
                in_bytes=math.prod(shape),
                out_bytes=math.prod(oshape) * out_elem,
                weight_bytes=math.prod(wshape)))
        shape = oshape
    return out
