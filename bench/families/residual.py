"""The residual family: a stem conv, a padded strided max pool, stages of
basic residual blocks, a global average pool and one linear classifier,
as the configuration's ``stem``, ``stem_pool`` and ``stages`` give them
(ResNet-34, arXiv 1512.03385, Table 1).

Weights (:func:`make_weights`), made on the device layer by layer, each
by a program jitted per layer shape (ResNet-34's repeated blocks share
one): 3-bit weight levels (int8), small int32 biases and float32
requantization multipliers, each multiplier mapping the 99.9th
percentile of its layer's non-negative accumulator, on a few seeded
calibration images, to the top level, as the ``sequential`` family
calibrates them.  Each block's ``res_scale`` row is the integer that
brings the skip operand to the spread of the ``conv_b`` accumulator it
joins (the float network adds two scaled tensors; here the skip enters
the accumulator's units), times a seeded factor of 1 or 2 per channel,
so that the row is not one number repeated.  The logit scale is the
configuration's constant.

The plain reference, written from the configuration file alone, in
straightforward ``jax.numpy`` with no kernels, padding tricks, bucketing
or caches:

* input:  ``q = clip(floor(x * 2^T), 0, 2^T - 1)`` (images in [0, 1))
* conv:   integer accumulator ``sum(q * w) + b`` (exact)
* requantize: ``clip(floor(acc * mult), 0, 2^T - 1)`` in float32
* max pool: the largest level of each window (zero pads: levels >= 0)
* block:  ``a = requant(conv3x3_s(x) + b_a)``; ``skip`` is ``x`` or
  ``conv1x1_s(x) + b_p`` (int32, no requantization);
  ``out = requant(conv3x3_1(a) + b_b + res_scale * skip)``
* global average pool: the sum of the levels over the map (the 1/HW
  division belongs to the classifier's scale)
* classifier: float logits ``(sum . w + b) * logit_scale``

In float32 every integer formed is below 2^24 in magnitude (a test
bounds it on the seeded weights), so the convolutions at
``precision=HIGHEST`` are exact.  ``dtype=jnp.bfloat16`` is the control.
"""

from __future__ import annotations

import functools
import math
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

import netgen
import work

HIGHEST = jax.lax.Precision.HIGHEST
KERNELS = {"conv": "radix_conv2d_pallas", "conv_res": "radix_conv2d_res_pallas",
           "matmul": "radix_matmul_pallas"}
BLOCK_PAD = ((1, 1), (1, 1))


# ---------------------------------------------------------------------------
# Shapes.
# ---------------------------------------------------------------------------


def _pads(p):
    return tuple((int(a), int(b)) for a, b in p)


def _out(size: int, k: int, stride: int, pads) -> int:
    return (size + pads[0] + pads[1] - k) // stride + 1


def plan(cfg: dict) -> dict:
    """The network's layers with every shape: ``stem`` and ``pool``
    (H, W, C out), ``blocks`` (cin, cout, stride, proj, H_in, H_out) and
    the global pool's window."""
    h, w, c = cfg["input_hw"]
    st = cfg["stem"]
    sp = _pads(st["padding"])
    h, w = (_out(h, st["kernel"], st["stride"], sp[0]),
            _out(w, st["kernel"], st["stride"], sp[1]))
    c = st["out"]
    stem = {"kernel": st["kernel"], "stride": st["stride"], "padding": sp,
            "cin": cfg["input_hw"][2], "cout": c, "hw": (h, w)}
    pl_ = cfg["stem_pool"]
    pp = _pads(pl_["padding"])
    h, w = (_out(h, pl_["window"], pl_["stride"], pp[0]),
            _out(w, pl_["window"], pl_["stride"], pp[1]))
    pool = {"window": pl_["window"], "stride": pl_["stride"], "padding": pp,
            "hw": (h, w)}
    blocks = []
    for stage in cfg["stages"]:
        for i in range(stage["blocks"]):
            s = stage["stride"] if i == 0 else 1
            cout = stage["out"]
            h2, w2 = _out(h, 3, s, (1, 1)), _out(w, 3, s, (1, 1))
            blocks.append({"cin": c, "cout": cout, "stride": s,
                           "proj": s != 1 or c != cout,
                           "hw_in": (h, w), "hw": (h2, w2)})
            h, w, c = h2, w2, cout
    assert h == w, (h, w)
    return {"stem": stem, "pool": pool, "blocks": blocks, "gap": h,
            "features": c}


# ---------------------------------------------------------------------------
# The plain reference.
# ---------------------------------------------------------------------------


def levels(cfg: dict) -> int:
    return 2 ** int(cfg["num_steps"])


def quantize_input(cfg: dict, x, dtype=jnp.float32):
    q = jnp.floor(x.astype(dtype) * jnp.asarray(levels(cfg), dtype))
    return jnp.clip(q, 0, levels(cfg) - 1)


def conv(q, w, stride: int, padding, dtype=jnp.float32):
    """Integer accumulator of one conv (bias excluded)."""
    return jax.lax.conv_general_dilated(
        q.astype(dtype), w.astype(dtype), window_strides=(stride, stride),
        padding=padding, dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HIGHEST, preferred_element_type=dtype)


def requantize(cfg: dict, acc, mult, dtype=jnp.float32):
    top = levels(cfg) - 1
    return jnp.clip(jnp.floor(acc.astype(dtype) * mult.astype(dtype)), 0, top)


def max_pool(q, window: int, stride: int, padding):
    (a, b), (c, d) = padding
    return jax.lax.reduce_window(
        q, jnp.zeros((), q.dtype), jax.lax.max, (1, window, window, 1),
        (1, stride, stride, 1), ((0, 0), (a, b), (c, d), (0, 0)))


def block_join(cfg: dict, blk: dict, p: dict, q, dtype=jnp.float32):
    """The integers a block joins (``conv_b + b_b + res_scale * skip``)
    and its conv_a levels."""
    s = blk["stride"]
    a = requantize(cfg, conv(q, p["a"]["w"], s, BLOCK_PAD, dtype)
                   + p["a"]["b"].astype(dtype), p["a"]["mult"], dtype)
    if p["proj"] is None:
        skip = q
    else:
        skip = (conv(q, p["proj"]["w"], s, "VALID", dtype)
                + p["proj"]["b"].astype(dtype))
    acc = (conv(a, p["b"]["w"], 1, BLOCK_PAD, dtype)
           + p["b"]["b"].astype(dtype)
           + p["res_scale"].astype(dtype) * skip)
    return acc, a


def forward(cfg: dict, weights: dict, x, *, dtype=jnp.float32):
    """(n, H, W, C) float images -> (n, classes) float32 logits."""
    net = plan(cfg)
    st, pool = net["stem"], net["pool"]
    q = quantize_input(cfg, x, dtype)
    p = weights["stem"]
    q = requantize(cfg, conv(q, p["w"], st["stride"], st["padding"], dtype)
                   + p["b"].astype(dtype), p["mult"], dtype)
    q = max_pool(q, pool["window"], pool["stride"], pool["padding"])
    for blk, p in zip(net["blocks"], weights["blocks"]):
        acc, _ = block_join(cfg, blk, p, q, dtype)
        q = requantize(cfg, acc, p["b"]["mult"], dtype)
    feat = q.sum(axis=(1, 2))                    # global average pool, x HW
    p = weights["fc"]
    acc = jnp.dot(feat, p["w"].astype(dtype), precision=HIGHEST,
                  preferred_element_type=dtype) + p["b"].astype(dtype)
    return (acc * weights["logit_scale"].astype(dtype)).astype(jnp.float32)


def make_forward(cfg: dict, dtype=jnp.float32):
    """Jitted ``forward(weights, x)`` for one configuration."""
    return jax.jit(lambda weights, x: forward(cfg, weights, x, dtype=dtype))


# ---------------------------------------------------------------------------
# Seeded weights.
# ---------------------------------------------------------------------------


def _draw(key, shape, qmax: int, acc_of):
    """Seeded weight levels and bias of one layer and its accumulator with
    the bias; the key chain's next key."""
    kw, kb, key = jax.random.split(key, 3)
    w = jax.random.randint(kw, shape, -qmax, qmax + 1, jnp.int8)
    acc = acc_of(w)
    spread = jnp.maximum(jnp.std(acc), 1.0)
    b = jnp.round(jax.random.normal(kb, (shape[-1],)) * 0.1 * spread
                  ).astype(jnp.int32)
    return key, w, b, acc + b.astype(jnp.float32)


def _percentile(x, pct: float):
    """``jnp.percentile(x, pct)`` (linear interpolation) of an
    integer-valued float32 array, its two order statistics found by
    bisection on the value: 32 counting reductions each, where the sort
    behind ``jnp.percentile`` takes about two minutes to compile for a
    v5e at ResNet-34's calibration sizes."""
    v = x.reshape(-1).astype(jnp.int32)
    q = jnp.float32(pct) / 100 * (jnp.float32(v.size) - 1)
    low, high = jnp.floor(q), jnp.ceil(q)
    w_high = q - low

    def order_stat(k):
        def halve(_, bounds):
            lo, hi = bounds
            mid = lo + (hi - lo) // 2
            at_most = jnp.sum(v <= mid) > k
            return jnp.where(at_most, lo, mid + 1), jnp.where(at_most, mid, hi)
        return jax.lax.fori_loop(0, 32, halve, (jnp.min(v), jnp.max(v)))[0]

    lo_v = order_stat(low.astype(jnp.int32)).astype(jnp.float32)
    hi_v = order_stat(high.astype(jnp.int32)).astype(jnp.float32)
    return lo_v * (1 - w_high) + hi_v * w_high


def _mult(acc, num_steps: int):
    hi = _percentile(jnp.maximum(acc, 0.0), 99.9)
    return (jnp.float32(2 ** num_steps) / jnp.maximum(hi, 1.0)).astype(
        jnp.float32)


@functools.partial(jax.jit, static_argnames=(
    "cout", "kernel", "stride", "padding", "num_steps", "qmax"))
def _stem_weights(key, q, *, cout, kernel, stride, padding, num_steps,
                  qmax):
    """(next key, the stem's arrays, its output levels)."""
    key, w, b, acc = _draw(key, (kernel, kernel, q.shape[-1], cout), qmax,
                           lambda w: conv(q, w, stride, padding))
    mult = _mult(acc, num_steps)
    return key, {"w": w, "b": b, "mult": mult}, requantize(
        {"num_steps": num_steps}, acc, mult)


@functools.partial(jax.jit, static_argnames=(
    "cout", "stride", "proj", "num_steps", "qmax"))
def _block_weights(key, q, *, cout, stride, proj, num_steps, qmax):
    """(next key, one block's arrays, its output levels), calibrated on
    the levels ``q`` the block reads."""
    lv, cin = {"num_steps": num_steps}, q.shape[-1]
    key, wa, ba, acc_a = _draw(key, (3, 3, cin, cout), qmax,
                               lambda w: conv(q, w, stride, BLOCK_PAD))
    ma = _mult(acc_a, num_steps)
    a = requantize(lv, acc_a, ma)
    pj, skip = None, q
    if proj:
        key, wp, bp, skip = _draw(key, (1, 1, cin, cout), qmax,
                                  lambda w: conv(q, w, stride, "VALID"))
        pj = {"w": wp, "b": bp}
    key, wb, bb, acc_b = _draw(key, (3, 3, cout, cout), qmax,
                               lambda w: conv(a, w, 1, BLOCK_PAD))
    # the skip enters at the spread of the accumulator it joins, times a
    # seeded 1 or 2 per channel
    r = jnp.maximum(jnp.round(jnp.std(acc_b)
                              / jnp.maximum(jnp.std(skip), 1.0)), 1.0)
    factor = jax.random.randint(jax.random.fold_in(key, 1), (cout,), 1, 3)
    res_scale = (r * factor).astype(jnp.int32)
    acc = acc_b + res_scale.astype(jnp.float32) * skip
    mb = _mult(acc, num_steps)
    return key, {"a": {"w": wa, "b": ba, "mult": ma},
                 "b": {"w": wb, "b": bb, "mult": mb},
                 "proj": pj, "res_scale": res_scale}, requantize(lv, acc, mb)


@functools.partial(jax.jit, static_argnames=("classes", "qmax"))
def _fc_weights(key, q, *, classes, qmax):
    feat = q.sum(axis=(1, 2))
    _, w, b, _ = _draw(key, (feat.shape[-1], classes), qmax,
                       lambda w: jnp.dot(feat, w.astype(jnp.float32),
                                         precision=HIGHEST))
    return {"w": w, "b": b}


def make_weights(cfg: dict, key) -> dict:
    """Every layer's served integers, from ``key``: the stem, each block
    and the classifier drawn in turn and calibrated on seeded images."""
    num_steps = int(cfg["num_steps"])
    qmax = 2 ** (int(cfg["weight_bits"]) - 1) - 1
    net = plan(cfg)
    kcal, key = jax.random.split(key)
    q = quantize_input(
        cfg, netgen.images(cfg, kcal, int(cfg["calib_images"])))
    st, pool = net["stem"], net["pool"]
    key, stem, q = _stem_weights(
        key, q, cout=st["cout"], kernel=st["kernel"], stride=st["stride"],
        padding=st["padding"], num_steps=num_steps, qmax=qmax)
    q = max_pool(q, pool["window"], pool["stride"], pool["padding"])
    blocks = []
    for blk in net["blocks"]:
        key, p, q = _block_weights(
            key, q, cout=blk["cout"], stride=blk["stride"],
            proj=blk["proj"], num_steps=num_steps, qmax=qmax)
        blocks.append(p)
    return {"stem": stem, "blocks": blocks,
            "fc": _fc_weights(key, q, classes=int(cfg["num_classes"]),
                              qmax=qmax),
            "logit_scale": jnp.float32(cfg["logit_scale"])}


def join_bound(cfg: dict, weights: dict) -> int:
    """The largest magnitude any block's join can form, over every input:
    per output channel, ``sum|w_b| * top + |b_b| + res_scale * max|skip|``
    (the float32 reference is exact while this stays below 2^24)."""
    top = levels(cfg) - 1
    worst = 0
    for p in weights["blocks"]:
        def reach(q):
            w = np.abs(np.asarray(q["w"], np.int64))
            return w.reshape(-1, w.shape[-1]).sum(0) * top + np.abs(
                np.asarray(q["b"], np.int64))
        skip = top if p["proj"] is None else reach(p["proj"])
        worst = max(worst, int((reach(p["b"]) + np.asarray(
            p["res_scale"], np.int64) * skip).max()))
    return worst


def program_net(cfg: dict, weights: dict):
    """The program's ``QuantizedNet`` over the benchmark's arrays."""
    from repro.core import conversion, encoding

    if cfg["encoding"] != "radix":
        raise ValueError(f"encoding {cfg['encoding']!r}")
    net = plan(cfg)
    st, pool = net["stem"], net["pool"]
    p = weights["stem"]
    static = [("conv", {"stride": st["stride"], "padding": st["padding"]}),
              ("pool", {"window": pool["window"], "stride": pool["stride"],
                        "padding": pool["padding"], "mode": "max"})]
    qlayers = [{"w_q": p["w"], "b_int": p["b"], "mult": p["mult"]}, None]

    def q(p):
        return {"w_q": p["w"], "b_int": p["b"], "mult": p.get("mult")}

    for blk, p in zip(net["blocks"], weights["blocks"]):
        static.append(("block", {"stride": blk["stride"],
                                 "proj": blk["proj"]}))
        qlayers.append({"a": q(p["a"]), "b": q(p["b"]),
                        "proj": None if p["proj"] is None else q(p["proj"]),
                        "res_scale": p["res_scale"]})
    static += [("pool", {"window": net["gap"], "mode": "avg"}),
               ("flatten", {}), ("linear", {})]
    qlayers += [None, None, {"w_q": weights["fc"]["w"],
                             "b_int": weights["fc"]["b"], "mult": None}]
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
    """Per kernel call, in plan order: the stem, then per block conv_a,
    the projection (where there is one) and conv_b with its join, then
    the classifier.  The join's ``in_bytes`` hold its skip operand (uint8
    levels, or a projection's int32); the classifier reads the global
    pool's 10-bit carry as int32."""
    net = plan(cfg)
    st = net["stem"]
    hw0 = math.prod(cfg["input_hw"][:2])
    k = st["kernel"]
    hw = math.prod(st["hw"])
    out = [work.LayerWork(
        name="conv1", kernel="conv", macs=hw * k * k * st["cin"] * st["cout"],
        in_bytes=hw0 * st["cin"], out_bytes=hw * st["cout"],
        weight_bytes=k * k * st["cin"] * st["cout"])]
    for i, blk in enumerate(net["blocks"], 1):
        cin, cout = blk["cin"], blk["cout"]
        hw_in, hw = math.prod(blk["hw_in"]), math.prod(blk["hw"])
        out.append(work.LayerWork(
            name=f"block{i}.a", kernel="conv", macs=hw * 9 * cin * cout,
            in_bytes=hw_in * cin, out_bytes=hw * cout,
            weight_bytes=9 * cin * cout))
        skip = hw * cout
        if blk["proj"]:
            out.append(work.LayerWork(
                name=f"block{i}.proj", kernel="conv", macs=hw * cin * cout,
                in_bytes=hw_in * cin, out_bytes=4 * hw * cout,
                weight_bytes=cin * cout))
            skip *= 4
        out.append(work.LayerWork(
            name=f"block{i}.b", kernel="conv_res", macs=hw * 9 * cout * cout,
            in_bytes=hw * cout + skip, out_bytes=hw * cout,
            weight_bytes=9 * cout * cout))
    f, n = net["features"], int(cfg["num_classes"])
    out.append(work.LayerWork(name="fc1", kernel="matmul", macs=f * n,
                              in_bytes=4 * f, out_bytes=4 * n,
                              weight_bytes=f * n))
    return out
