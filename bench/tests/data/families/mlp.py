"""Test-only family: a radix-coded multilayer perceptron.

Its configuration has a schema of its own: the widths of the hidden
layers in ``hidden`` and a ``num_classes`` head, no ``layers`` list.
The program serves it as ``flatten`` then ``linear`` layers.  It enters
the harness as this file, its configuration and a workload, with no
edit to any other file.  ``CALLS`` counts the harness's calls into it.

* input:  ``q = clip(floor(x * 2^T), 0, 2^T - 1)``, flattened
* linear: ``acc = q @ w + b``; hidden layers requantize
  ``clip(floor(acc * mult), 0, 2^T - 1)`` with ``mult = 4 / fan_in``
* logits: ``acc * logit_scale``
"""

import collections
import math

import jax
import jax.numpy as jnp
import numpy as np

import work

HIGHEST = jax.lax.Precision.HIGHEST
KERNELS = {"matmul": "radix_matmul_pallas"}
CALLS = collections.Counter()


def widths(cfg: dict):
    """(fan-in, fan-out) of every linear layer."""
    sizes = ([math.prod(cfg["input_hw"])] + list(cfg["hidden"])
             + [cfg["num_classes"]])
    return list(zip(sizes, sizes[1:]))


def _make_weights(cfg: dict, key):
    qmax = 2 ** (int(cfg["weight_bits"]) - 1) - 1
    shapes = widths(cfg)
    out = []
    for i, (fan_in, fan_out) in enumerate(shapes):
        kw, kb, key = jax.random.split(key, 3)
        last = i == len(shapes) - 1
        out.append({
            "w": jax.random.randint(kw, (fan_in, fan_out), -qmax, qmax + 1,
                                    jnp.int8),
            "b": jax.random.randint(kb, (fan_out,), -8, 9, jnp.int32),
            "mult": None if last else jnp.float32(4.0 / fan_in)})
    return {"layers": out, "logit_scale": jnp.float32(cfg["logit_scale"])}


def make_weights(cfg: dict, key) -> dict:
    CALLS["make_weights"] += 1
    return jax.jit(lambda k: _make_weights(cfg, k))(key)


def program_net(cfg: dict, weights: dict):
    from repro.core import conversion, encoding

    CALLS["program_net"] += 1
    n = len(weights["layers"])
    return conversion.QuantizedNet(
        static=(("flatten", {}),) + (("linear", {}),) * n,
        num_steps=int(cfg["num_steps"]),
        weight_bits=int(cfg["weight_bits"]),
        qlayers=[None] + [{"w_q": p["w"], "b_int": p["b"],
                           "mult": p["mult"]} for p in weights["layers"]],
        input_scale=1.0,
        logit_scale=float(np.asarray(weights["logit_scale"])),
        encoding=encoding.RadixEncoding(int(cfg["num_steps"])),
    )


def make_forward(cfg: dict, dtype=jnp.float32):
    CALLS["make_forward"] += 1
    top = 2 ** int(cfg["num_steps"]) - 1

    def forward(weights, x):
        q = jnp.clip(jnp.floor(x.astype(dtype) * (top + 1)), 0, top)
        q = q.reshape(q.shape[0], -1)
        for p in weights["layers"]:
            acc = jnp.dot(q, p["w"].astype(dtype), precision=HIGHEST,
                          preferred_element_type=dtype) + p["b"].astype(dtype)
            if p["mult"] is None:
                return (acc * weights["logit_scale"].astype(dtype)
                        ).astype(jnp.float32)
            q = jnp.clip(jnp.floor(acc * p["mult"].astype(dtype)), 0, top)
    return jax.jit(forward)


def layers(cfg: dict):
    CALLS["layers"] += 1
    shapes = widths(cfg)
    return [work.LayerWork(name=f"fc{i + 1}", kernel="matmul",
                           macs=fan_in * fan_out, in_bytes=fan_in,
                           out_bytes=fan_out * (4 if i == len(shapes) - 1
                                                else 1),
                           weight_bytes=fan_in * fan_out)
            for i, (fan_in, fan_out) in enumerate(shapes)]
