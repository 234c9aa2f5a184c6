"""Residual networks on the compiled plan (DESIGN.md §2, "Residual
blocks").

A tiny ResNet-shaped net — 16x16x3 input, a 3x3 stem with explicit pads,
a 3x3/2 max pool padded by one, one identity block, one stride-2 block
with a 1x1 projection, a global average pool and a 10-class classifier —
compiled through ``Accelerator.compile`` in both dataflows must equal the
jnp oracle (packed and spike-plane modes) and a plain integer reference
written here, bit for bit.  The kernel's residual epilogue, explicit
conv pads, the stride fold and the strided padded pools are pinned on
their own.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import conversion, encoding, engine, layers
from repro.kernels.radix_conv import (
    fold_stride_input,
    fold_stride_weights,
    radix_conv2d_res_pallas,
)
from repro.launch import serve_cnn

RNG = np.random.default_rng(34)
PAD1 = ((1, 1), (1, 1))


def _w(*shape):
    return jnp.asarray(RNG.integers(-3, 4, shape), jnp.int8)


def _b(n):
    return jnp.asarray(RNG.integers(-20, 20, (n,)), jnp.int32)


# ---------------------------------------------------------------------------
# A plain integer reference, independent of the engine's oracle.
# ---------------------------------------------------------------------------


def _conv(q, w, stride=1, padding="VALID"):
    return jax.lax.conv_general_dilated(
        jnp.asarray(q, jnp.int32), jnp.asarray(w, jnp.int32),
        (stride, stride), padding, dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.int32)


def _requant(acc, mult, T):
    return np.clip(np.floor(np.asarray(acc, np.float32)
                            * np.asarray(mult, np.float32)), 0, 2 ** T - 1)


def _max_pool(q, window, stride, pad):
    (a, b), (c, d) = pad
    q = np.pad(np.asarray(q), ((0, 0), (a, b), (c, d), (0, 0)))
    n, h, w, ch = q.shape
    ho, wo = (h - window) // stride + 1, (w - window) // stride + 1
    out = np.zeros((n, ho, wo, ch), q.dtype)
    for i in range(ho):
        for j in range(wo):
            out[:, i, j] = q[:, i * stride:i * stride + window,
                             j * stride:j * stride + window].max(axis=(1, 2))
    return out


def plain_reference(qnet, x):
    """Float logits of the tiny net from the integer semantics alone."""
    T = qnet.num_steps
    q = np.clip(np.floor(np.asarray(x) * 2 ** T), 0, 2 ** T - 1)
    for (kind, cfg), p in zip(qnet.static, qnet.qlayers):
        if kind == "conv":
            acc = _conv(q, p["w_q"], cfg["stride"], cfg["padding"]) + p["b_int"]
            q = _requant(acc, p["mult"], T)
        elif kind == "pool" and cfg["mode"] == "max":
            q = _max_pool(q, cfg["window"], cfg.get("stride", cfg["window"]),
                          cfg.get("padding", ((0, 0), (0, 0))))
        elif kind == "pool":
            q = np.asarray(q).sum(axis=(1, 2), keepdims=True)
        elif kind == "block":
            s = cfg["stride"]
            a = _requant(_conv(q, p["a"]["w_q"], s, PAD1) + p["a"]["b_int"],
                         p["a"]["mult"], T)
            skip = (np.asarray(q, np.int64) if p["proj"] is None else
                    np.asarray(_conv(q, p["proj"]["w_q"], s) + p["proj"]["b_int"]))
            acc = (np.asarray(_conv(a, p["b"]["w_q"], 1, PAD1) + p["b"]["b_int"])
                   + np.asarray(p["res_scale"]) * skip)
            q = _requant(acc, p["b"]["mult"], T)
        elif kind == "flatten":
            q = np.asarray(q).reshape(q.shape[0], -1)
        else:
            acc = np.asarray(q, np.int64) @ np.asarray(p["w_q"], np.int64)
            acc = acc + np.asarray(p["b_int"])
            return (acc.astype(np.float32)
                    * np.float32(qnet.logit_scale)).astype(np.float32)
    raise AssertionError("no classifier")


# ---------------------------------------------------------------------------
# The tiny residual net, calibrated on its own input.
# ---------------------------------------------------------------------------


def tiny_resnet(T=4, batch=6, hw=16, c=8):
    """(qnet, x): stem 3x3 pad (1,1) -> max pool 3x3/2 pad (1,1) -> an
    identity block (c -> c) -> a stride-2 projection block (c -> 2c) ->
    global average pool -> flatten -> 10 classes."""
    spec = encoding.RadixEncoding(T)
    x = jnp.asarray(RNG.uniform(0, 1, (batch, hw, hw, 3)), jnp.float32)

    def mult(acc):
        hi = float(np.percentile(np.maximum(np.asarray(acc), 0), 99.5))
        return jnp.float32(2 ** T / max(hi, 1.0))

    q = spec.quantize(x)
    w, b = _w(3, 3, 3, c), _b(c)
    acc = layers.q_conv2d(q, w, b, padding=PAD1)
    static = [("conv", {"stride": 1, "padding": PAD1}),
              ("pool", {"window": 3, "stride": 2, "padding": PAD1,
                        "mode": "max"})]
    qlayers = [{"w_q": w, "b_int": b, "mult": mult(acc)}, None]
    q = layers.q_max_pool(spec.requantize(acc, qlayers[0]["mult"]), 3, 2,
                          PAD1)
    for cin, cout, s in ((c, c, 1), (c, 2 * c, 2)):
        proj = s != 1 or cin != cout
        wa, ba = _w(3, 3, cin, cout), _b(cout)
        acc_a = layers.q_conv2d(q, wa, ba, stride=s, padding=PAD1)
        a = spec.requantize(acc_a, mult(acc_a))
        pj, skip = None, q.astype(jnp.int32)
        if proj:
            pj = {"w_q": _w(1, 1, cin, cout), "b_int": _b(cout)}
            skip = layers.q_conv2d(q, pj["w_q"], pj["b_int"], stride=s)
        wb, bb = _w(3, 3, cout, cout), _b(cout)
        r = jnp.asarray(RNG.integers(1, 9, (cout,)), jnp.int32)
        acc_b = layers.q_conv2d(a, wb, bb, padding=PAD1) + r * skip
        static.append(("block", {"stride": s, "proj": proj}))
        qlayers.append({"a": {"w_q": wa, "b_int": ba, "mult": mult(acc_a)},
                        "b": {"w_q": wb, "b_int": bb, "mult": mult(acc_b)},
                        "proj": pj, "res_scale": r})
        q = spec.requantize(acc_b, qlayers[-1]["b"]["mult"])
    gap = q.shape[1]
    static += [("pool", {"window": gap, "mode": "avg"}), ("flatten", {}),
               ("linear", {})]
    qlayers += [None, None, {"w_q": _w(2 * c, 10), "b_int": _b(10),
                             "mult": None}]
    qnet = conversion.QuantizedNet(
        static=tuple(static), num_steps=T, weight_bits=3, qlayers=qlayers,
        input_scale=1.0, logit_scale=0.01, encoding=spec)
    return qnet, x


@pytest.fixture(scope="module")
def tiny():
    return tiny_resnet()


def test_oracle_modes_equal_the_plain_reference(tiny):
    qnet, x = tiny
    want = plain_reference(qnet, x)
    for mode in ("packed", "snn"):
        np.testing.assert_array_equal(
            np.asarray(api.oracle(qnet, x, mode=mode)), want)


@pytest.mark.parametrize("dataflow", ["fused", "bitserial"])
def test_compiled_plan_equals_oracle(tiny, dataflow):
    """Batch 6 over bucket 4: one full chunk and one padded tail."""
    qnet, x = tiny
    exe = api.Accelerator(dataflow=dataflow).compile(qnet, x.shape[1:],
                                                     buckets=(4,))
    got = np.asarray(exe(x))
    np.testing.assert_array_equal(got, np.asarray(api.oracle(qnet, x)))
    np.testing.assert_array_equal(got, plain_reference(qnet, x))


def test_wide_global_pool_carry_reaches_the_classifier():
    """T=5: the 4x4 global sum reaches 496 (9 bits), so the pool's carry
    leaves the byte format and the classifier reads int32, as ResNet-34's
    7x7 pool (735, 10 bits) does at T=4."""
    qnet, x = tiny_resnet(T=5, batch=2)
    assert layers.sum_pool_bits(5, 4) == 9
    exe = api.Accelerator().compile(qnet, x.shape[1:], buckets=(2,))
    assert exe.traffic()["layers"][-2]["out_dtype"] == "int32"
    np.testing.assert_array_equal(np.asarray(exe(x)),
                                  plain_reference(qnet, x))


def test_server_returns_the_oracles_logits(tiny):
    qnet, x = tiny
    srv = serve_cnn.CNNServer(qnet, tuple(x.shape[1:]), buckets=(2, 4))
    srv.warmup()
    np.testing.assert_array_equal(np.asarray(srv.infer(x[:3])),
                                  np.asarray(api.oracle(qnet, x[:3])))


def test_layer_map_and_skip_traffic(tiny):
    """Each block runs under ``block<i>``; the joins read the identity's
    uint8 levels and the projection's int32 accumulators."""
    qnet, x = tiny
    exe = api.Accelerator().compile(qnet, x.shape[1:], buckets=(4,))
    plan = exe.plan_for(4)
    scopes = set(plan.layer_map().values())
    assert {"block1", "block2", "conv1", "pool1", "pool2", "fc1"} <= scopes
    traffic = plan.activation_traffic()
    # identity: 4 x 8x8x8 levels; projection: 4 x 4x4x16 int32
    assert traffic["skip_read_bytes"] == 4 * 8 * 8 * 8 + 4 * 4 * 4 * 16 * 4
    assert [l["skip_read_bytes"] for l in traffic["layers"]] == [
        0, 0, 2048, 4096, 0, 0]
    # T plane passes per conv (stem, 2 + 3 block convs), 8 for the
    # classifier over the 4x4 pool's carry, per batch shard
    assert plan.plane_passes_per_call == plan.data_parallel * (
        4 * (1 + 2 + 3) + 8)


@pytest.mark.parametrize("pick", ["xla", "xla_joins", "pallas_joins"])
def test_block_convs_take_their_tuned_strategy(pick, monkeypatch):
    """Every block conv resolves its strategy as a plain conv does: the
    tuner is asked for each, the join under a key of its own.  At width
    12 the Pallas path pads to 16 lanes and the XLA twin keeps 12, so
    mixing them hands a join a skip operand of the other width; the XLA
    twin's join and every mix stay bit-exact."""
    from repro.kernels import autotune as at
    from repro.kernels.autotune import KernelConfig

    keys = []

    def tune(key, cands, build):
        keys.append(key)
        joined = key[-1] == "joined"
        xla = {"xla": True, "xla_joins": joined,
               "pallas_joins": not joined}[pick]
        return KernelConfig(impl="xla") if xla else KernelConfig()

    monkeypatch.setattr(at, "tune", tune)
    qnet, x = tiny_resnet(batch=2, c=12)
    plan = engine._compile_plan_impl(qnet, (2,) + tuple(x.shape[1:]),
                                     autotune=True)
    rows = plan.tuned_tiles
    assert [r["layer"] for r in rows][1:6] == [
        "block1.a", "block1.b", "block2.a", "block2.proj", "block2.b"]
    assert all(r["tuned"] for r in rows) and len(keys) == len(rows)
    assert [k[-1] == "joined" for k in keys] == [
        False, False, True, False, False, True, False]
    np.testing.assert_array_equal(np.asarray(plan(x)),
                                  plain_reference(qnet, x))


@pytest.mark.parametrize("enc", [encoding.RateEncoding(7),
                                 encoding.PhaseEncoding(8, periods=2)],
                         ids=["rate", "phase"])
def test_other_encodings_refuse_blocks_at_compile(tiny, enc):
    qnet, x = tiny
    # the block alone: rate coding refuses the max pool in front of it too
    other = dataclasses.replace(qnet, static=qnet.static[2:3],
                                qlayers=qnet.qlayers[2:3],
                                num_steps=enc.num_steps, encoding=enc)
    with pytest.raises(ValueError, match="'block'"):
        api.Accelerator(backend="jnp").compile(other, x.shape[1:])


def test_memory_report_refuses_blocks(tiny):
    qnet, _ = tiny
    with pytest.raises(ValueError, match="'block'"):
        engine.memory_report(qnet, (16, 16, 3))


# ---------------------------------------------------------------------------
# The kernel's residual epilogue.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("skip_dtype", ["uint8", "int32"])
@pytest.mark.parametrize("method,plane_parallel", [
    ("fused", False), ("bitserial", False), ("bitserial", True)])
def test_residual_epilogue_is_requantize_of_the_join(skip_dtype, method,
                                                     plane_parallel):
    """``floor(f32(acc + b + r * skip) * mult)`` clamped, as
    ``layers.q_requantize`` computes it, with outputs on both clamp edges
    and the padding lanes (mult 0, r 0) inert at level 0."""
    T, cout, cop = 4, 12, 16
    x = jnp.asarray(RNG.integers(0, 16, (2, 7, 7, 8)), jnp.uint8)
    w = _w(3, 3, 8, cout)
    b = _b(cout)
    if skip_dtype == "uint8":
        skip = RNG.integers(0, 16, (2, 5, 5, cout))
    else:
        skip = RNG.integers(-400, 400, (2, 5, 5, cout))
    skip = jnp.asarray(skip, skip_dtype)
    r = jnp.asarray(RNG.integers(1, 40, (cout,)), jnp.int32)
    mult = jnp.asarray(RNG.uniform(0.01, 0.06, (cout,)), jnp.float32)
    acc = _conv(x, w) + b + r * skip.astype(jnp.int32)
    want = np.asarray(layers.q_requantize(acc, T, mult))
    raw = np.floor(np.asarray(acc, np.float32) * np.asarray(mult))
    assert (raw < 0).any() and (raw > 15).any()      # both clamp edges

    def pad(a, value=0):
        return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, cop - cout)],
                       constant_values=value)

    got = radix_conv2d_res_pallas(
        x, pad(w), pad(skip, 7), pad(r).reshape(1, cop), num_steps=T,
        method=method, bco=8, interpret=True, bias=pad(b).reshape(1, cop),
        mult=pad(mult).reshape(1, cop), plane_parallel=plane_parallel)
    assert got.dtype == jnp.uint8 and got.shape == (2, 5, 5, cop)
    np.testing.assert_array_equal(np.asarray(got)[..., :cout], want)
    assert not np.asarray(got)[..., cout:].any()


# ---------------------------------------------------------------------------
# Explicit pads, the stride fold and strided padded pools.
# ---------------------------------------------------------------------------


def _stem_net(hw, T=4):
    """A 7x7/2 conv padded (3, 3), a 3x3/2 max pool padded (1, 1), and a
    classifier: ResNet's stem at a small size."""
    x = jnp.asarray(RNG.uniform(0, 1, (2, hw, hw, 3)), jnp.float32)
    w, b = _w(7, 7, 3, 8), _b(8)
    pad3 = ((3, 3), (3, 3))
    acc = layers.q_conv2d(encoding.quantize(x, T), w, b, stride=2,
                          padding=pad3)
    m = jnp.float32(2 ** T / max(float(np.percentile(np.asarray(acc), 99)),
                                 1.0))
    side = ((hw + 6 - 7) // 2 + 1 + 2 - 3) // 2 + 1
    static = (("conv", {"stride": 2, "padding": pad3}),
              ("pool", {"window": 3, "stride": 2, "padding": PAD1,
                        "mode": "max"}),
              ("flatten", {}), ("linear", {}))
    qlayers = [{"w_q": w, "b_int": b, "mult": m}, None, None,
               {"w_q": _w(side * side * 8, 5), "b_int": _b(5), "mult": None}]
    return conversion.QuantizedNet(
        static=static, num_steps=T, weight_bits=3, qlayers=qlayers,
        input_scale=1.0, logit_scale=0.01,
        encoding=encoding.RadixEncoding(T)), x


@pytest.mark.parametrize("hw", [16, 15], ids=["even", "odd"])
def test_explicit_pads_and_strided_pool_agree_with_oracle(hw):
    qnet, x = _stem_net(hw)
    want = np.asarray(api.oracle(qnet, x))
    np.testing.assert_array_equal(want, plain_reference(qnet, x))
    np.testing.assert_array_equal(
        np.asarray(api.oracle(qnet, x, mode="snn")), want)
    exe = api.Accelerator().compile(qnet, x.shape[1:], buckets=(2,))
    np.testing.assert_array_equal(np.asarray(exe(x)), want)


@pytest.mark.parametrize("k,stride,hw", [(7, 2, 22), (7, 2, 21), (3, 2, 10),
                                         (3, 2, 9), (1, 2, 8), (3, 3, 11)])
def test_stride_fold_is_the_strided_conv(k, stride, hw):
    """Space-to-depth input and weights give the strided conv exactly."""
    x = jnp.asarray(RNG.integers(0, 16, (2, hw, hw, 3)), jnp.int32)
    w = _w(k, k, 3, 4)
    want = _conv(x, w, stride)
    h_out = (hw - k) // stride + 1
    got = _conv(fold_stride_input(x, stride, k, k, h_out, h_out),
                fold_stride_weights(w, stride))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("padding", ["FULL", ((1, 1),), ((-1, 0), (0, 0))])
def test_unknown_padding_is_refused(padding):
    """No padding is silently taken for VALID any more."""
    with pytest.raises(ValueError, match="padding"):
        engine.conv_pads(padding, 8, 8, 3, 3, 1)


def test_plan_refuses_an_unknown_conv_padding():
    qnet, x = _stem_net(16)
    qnet.static = (("conv", {"stride": 2, "padding": "FULL"}),) \
        + qnet.static[1:]
    with pytest.raises(ValueError, match="FULL"):
        engine._compile_plan_impl(qnet, (2, 16, 16, 3))


@pytest.mark.parametrize("mode", ["or", "max", "avg"])
def test_strided_padded_pools_match_their_windows(mode):
    q = jnp.asarray(RNG.integers(0, 16, (2, 9, 9, 3)), jnp.uint8)
    fn = {"or": layers.q_or_pool, "max": layers.q_max_pool,
          "avg": layers.q_avg_pool}[mode]
    got = np.asarray(fn(q, 3, 2, PAD1)).astype(np.int64)
    p = np.pad(np.asarray(q, np.int64), ((0, 0), (1, 1), (1, 1), (0, 0)))
    assert got.shape == (2, 5, 5, 3)
    for i in range(5):
        for j in range(5):
            win = p[:, 2 * i:2 * i + 3, 2 * j:2 * j + 3]
            want = {"or": np.bitwise_or.reduce(np.bitwise_or.reduce(
                        win, axis=1), axis=1),
                    "max": win.max(axis=(1, 2)),
                    "avg": win.sum(axis=(1, 2))}[mode]
            np.testing.assert_array_equal(got[:, i, j], want)


def test_an_identity_block_that_changes_shape_is_refused(tiny):
    """A stride-2, 8 -> 16 block without projection weights cannot join
    its input: the plan says so instead of failing inside a kernel."""
    qnet, x = tiny
    qlayers = list(qnet.qlayers)
    qlayers[3] = {**qlayers[3], "proj": None}
    static = list(qnet.static)
    static[3] = ("block", {"stride": 2, "proj": False})
    bad = dataclasses.replace(qnet, static=tuple(static), qlayers=qlayers)
    with pytest.raises(ValueError, match="identity skip"):
        engine._compile_plan_impl(bad, (2,) + tuple(x.shape[1:]))
