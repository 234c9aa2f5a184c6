"""Seeded weights and input images for a configuration, made on the device.

``make_weights(cfg, key)`` builds every layer's served integers in one
jitted call: 3-bit weight levels (int8), small int32 biases and float32
requantization multipliers.  Each multiplier maps the 99.9th percentile of
its layer's non-negative accumulator, on a few seeded calibration images,
to the top level, as the paper's conversion calibrates.  The logit scale
is the configuration's constant ``logit_scale``: the program compiles it
into its plans, so a scale drawn from the seed would recompile them for
every seed.  The same arrays go to the program (:func:`quantized_net`)
and to :mod:`reference`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import reference


def root_key(seed: int):
    """PRNG key for any non-negative seed (64-bit seeds keep their high
    word)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


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


def images(cfg: dict, key, n: int):
    """``n`` seeded float32 images in [0, 1)."""
    return jax.random.uniform(key, (n,) + tuple(cfg["input_hw"]),
                              jnp.float32)


def _make_weights(cfg: dict, key):
    top = reference.levels(cfg) - 1
    qmax = 2 ** (int(cfg["weight_bits"]) - 1) - 1
    wshapes, _ = layer_shapes(cfg)
    n_affine = sum(s is not None for s in wshapes)
    kcal, key = jax.random.split(key)
    q = reference.quantize_input(
        cfg, images(cfg, kcal, int(cfg["calib_images"])))
    out, seen = [], 0
    for layer, shape in zip(cfg["layers"], wshapes):
        kind = layer["kind"]
        if kind == "pool":
            q = reference.or_pool(q, layer["window"])
            out.append(None)
            continue
        if kind == "flatten":
            q = q.reshape(q.shape[0], -1)
            out.append(None)
            continue
        seen += 1
        kw, kb, key = jax.random.split(key, 3)
        w = jax.random.randint(kw, shape, -qmax, qmax + 1, jnp.int8)
        acc = reference.affine(layer, q, w)
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
        q = reference.requantize(cfg, acc, mult)
        out.append({"w": w, "b": b, "mult": mult})
    return {"layers": out,
            "logit_scale": jnp.float32(cfg["logit_scale"])}


def make_weights(cfg: dict, key) -> dict:
    """Every layer's served integers, from ``key``, in one jitted call."""
    return jax.jit(lambda k: _make_weights(cfg, k))(key)


def quantized_net(cfg: dict, weights: dict):
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
