"""Ahead-of-time compiles of the serving kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it
compiles for a *described* v5e and refuses what the chip would refuse —
a lowering Mosaic has no rule for (an int32 x int32 dot), a block shape
off the (8, 128) tiling, more VMEM than a kernel may use.  Interpret mode
on the CPU checks none of that, so these compiles guard the main path's
kernels at their real widths (VGG-11 at 224x224, its 25088 -> 4096
classifier, decode attention at hd=128 over a 1024-slot cache) under
the default, untuned configuration.

The topology is described inside a module fixture and nowhere else:
only the worker that runs this file loads the TPU library.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import conversion, engine
from repro.kernels import ops
from repro.kernels.autotune import KernelConfig
from repro.kernels.radix_attn import radix_decode_attn_pallas
from repro.kernels.radix_conv import (
    radix_conv2d_pallas,
    radix_conv2d_res_pallas,
)
from repro.kernels.radix_matmul import OCC_LANES, radix_matmul_pallas

DEFAULT = KernelConfig()
T = 4


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _conv(h, cin, cout, *, k=3, method="fused", epilogue=True,
          plane_parallel=False, stride=1):
    """A conv layer as the plan builds it: SAME-padded input of side ``h``,
    Cout tiled by the default ``bco``, occupancy prepass on."""
    _, bco = ops._block(cout, pref=DEFAULT.bco)

    def fn(x, w, bias, mult, occ):
        return radix_conv2d_pallas(
            x, w, num_steps=T, method=method, bco=bco, stride=stride,
            bias=bias if epilogue else None, mult=mult if epilogue else None,
            occupancy=occ, mxu_dtype=DEFAULT.mxu_dtype,
            plane_parallel=plane_parallel)

    return fn, [((1, h, h, cin), jnp.uint8), ((k, k, cin, cout), jnp.int8),
                ((1, cout), jnp.int32), ((1, cout), jnp.float32),
                ((1, OCC_LANES), jnp.int32)]


def _joined_conv(h, c, skip_dtype):
    """A residual block's conv_b as the plan builds it: 3x3 over the
    padded conv_a levels, the skip operand joined in the epilogue."""
    _, bco = ops._block(c, pref=DEFAULT.bco)

    def fn(x, w, skip, scale, bias, mult, occ):
        return radix_conv2d_res_pallas(
            x, w, skip, scale, num_steps=T, method="fused", bco=bco,
            bias=bias, mult=mult, occupancy=occ, mxu_dtype=DEFAULT.mxu_dtype)

    return fn, [((2, h + 2, h + 2, c), jnp.uint8), ((3, 3, c, c), jnp.int8),
                ((2, h, h, c), skip_dtype), ((1, c), jnp.int32),
                ((1, c), jnp.int32), ((1, c), jnp.float32),
                ((1, OCC_LANES), jnp.int32)]


def _classifier(method):
    m, k, n = 8, 25088, 4096
    _, bm = ops._block(m, pref=DEFAULT.bm)

    def fn(x, w, bias, mult, occ):
        return radix_matmul_pallas(
            x, w, num_steps=T, method=method, bm=bm, bk=DEFAULT.bk,
            bn=DEFAULT.bn, bias=bias, mult=mult, occupancy=occ,
            mxu_dtype=DEFAULT.mxu_dtype)

    return fn, [((m, k), jnp.uint8), ((k, n), jnp.int8),
                ((1, n), jnp.int32), ((1, n), jnp.float32),
                ((1, OCC_LANES), jnp.int32)]


def _decode_attn(method, packed):
    rows, g, hd, s_len = 8, 8, 128, 1024
    hdp = hd // 2 if packed else hd

    def fn(qq, qs, kq, ks, vq, vs, mask, occ_k, occ_v):
        return radix_decode_attn_pallas(
            qq, qs, kq, ks, vq, vs, mask, occ_k, occ_v, num_steps=T, hd=hd,
            method=method, packed=packed, blk=DEFAULT.bk,
            mxu_dtype=DEFAULT.mxu_dtype)

    return fn, [((rows, g, hd), jnp.int32), ((rows, g), jnp.float32),
                ((rows, s_len, hdp), jnp.uint8), ((rows, s_len), jnp.float32),
                ((rows, s_len, hdp), jnp.uint8), ((rows, s_len), jnp.float32),
                ((rows, s_len), jnp.int32), ((1, OCC_LANES), jnp.int32),
                ((1, OCC_LANES), jnp.int32)]


CASES = {
    # VGG-11's two 224x224 convs: the row-tiled band must fit VMEM
    "vgg_conv1_226x226x3_64": lambda: _conv(226, 3, 64),
    "vgg_conv2_114x114x64_128": lambda: _conv(114, 64, 128),
    # the occupancy-gated lax.cond plane passes, sequential and per-plane
    "bitserial_conv_30x30x256": lambda: _conv(30, 256, 256,
                                              method="bitserial"),
    "bitserial_plane_parallel_conv_30x30x256": lambda: _conv(
        30, 256, 256, method="bitserial", plane_parallel=True),
    # ResNet-34's 7x7/2 stem over 230x230x3 (224 padded 3 each side): the
    # kernel's wrapper folds the stride into channels, 4x4 taps over
    # 115x115x12 (Mosaic refuses a strided conv's gathers)
    "resnet_stem_folded_115x115x12_64": lambda: _conv(230, 3, 64, k=7,
                                                      stride=2),
    # a 1x1/2 projection's raw int32 accumulator, 56x56x64 -> 28x28x128
    "resnet_proj_1x1s2_56x56x64_128": lambda: _conv(
        56, 64, 128, k=1, stride=2, epilogue=False),
    # the joins: uint8 identity at 56x56x64, int32 projection at 7x7x512
    "resnet_join_u8_56x56x64": lambda: _joined_conv(56, 64, jnp.uint8),
    "resnet_join_i32_7x7x512": lambda: _joined_conv(7, 512, jnp.int32),
    # LeNet's narrow-lane (Cout 16) 5x5 conv with a 10-wide output
    "lenet_conv2_14x14x8_16": lambda: _conv(14, 8, 16, k=5),
    "vgg_classifier_fused": lambda: _classifier("fused"),
    "vgg_classifier_bitserial": lambda: _classifier("bitserial"),
    "decode_attn_fused": lambda: _decode_attn("fused", False),
    "decode_attn_bitserial_packed": lambda: _decode_attn("bitserial", True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiles_for_v5e(case, one_chip):
    fn, shapes = CASES[case]()
    _compile(fn, shapes, one_chip)


def test_lenet_plan_keeps_kernel_names_and_maps_layers(one_chip,
                                                       monkeypatch):
    """A whole LeNet-5 plan, compiled for the chip: its Pallas custom
    calls keep the instruction-name prefixes a device trace is read by
    (``radix_conv2d_pallas.``, ``radix_matmul_pallas.``), and the plan's
    instruction -> layer map puts each of them under its own layer."""
    from repro.models import lenet

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    static, params, hw = lenet.make(pool_mode="or", width_mult=0.25)
    calib = jnp.asarray(np.random.default_rng(0).uniform(0, 1, (4,) + hw),
                        jnp.float32)
    qnet = conversion.convert(static, params, calib, num_steps=T)
    plan = engine._compile_plan_impl(qnet, (8,) + tuple(hw), method="fused")
    spec = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        plan._params)
    x = jax.ShapeDtypeStruct((8,) + tuple(hw), jnp.float32,
                             sharding=one_chip)
    text = plan._fn.lower(spec, x).compile().as_text()
    assert text.startswith("HloModule jit_plan_b8")
    calls = re.findall(r"^\s*%([\w.\-]+) = [^\n]*\bcustom-call\(",
                       text, re.M)
    layers = engine.layer_scopes(text)
    kinds = {"radix_conv2d_pallas.": "conv", "radix_matmul_pallas.": "fc"}
    got = []
    for name in calls:
        prefix = [p for p in kinds if name.startswith(p)]
        assert prefix, f"custom call {name} lost its kernel's name"
        assert layers[name].startswith(kinds[prefix[0]]), (name, layers)
        got.append(layers[name])
    assert sorted(got) == ["conv1", "conv2", "conv3", "fc1", "fc2", "fc3"]


def test_residual_plan_names_its_joins_and_fuses_them(one_chip,
                                                      monkeypatch):
    """A residual plan compiled for the chip: each block's joined conv
    keeps the ``radix_conv2d_res_pallas.`` prefix and plain convs keep
    ``radix_conv2d_pallas.``, all under the block's ``block<i>`` scope;
    no array-sized add sits in a block scope (the join is in the kernel's
    epilogue, the projection's bias in its bias row)."""
    from test_resnet_plan import tiny_resnet

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    qnet, _ = tiny_resnet()
    plan = engine._compile_plan_impl(qnet, (8, 16, 16, 3), method="fused")
    spec = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        plan._params)
    x = jax.ShapeDtypeStruct((8, 16, 16, 3), jnp.float32, sharding=one_chip)
    text = plan._fn.lower(spec, x).compile().as_text()
    layers = engine.layer_scopes(text)
    calls = re.findall(r"^\s*%([\w.\-]+) = [^\n]*\bcustom-call\(",
                       text, re.M)
    got = sorted((layers[c], c.rsplit(".", 1)[0]) for c in calls)
    assert got == [("block1", "radix_conv2d_pallas"),
                   ("block1", "radix_conv2d_res_pallas"),
                   ("block2", "radix_conv2d_pallas"),
                   ("block2", "radix_conv2d_pallas"),
                   ("block2", "radix_conv2d_res_pallas"),
                   ("conv1", "radix_conv2d_pallas"),
                   ("fc1", "radix_matmul_pallas")]
    adds = re.findall(r"^\s*%(add[\w.\-]*) = s32\[([\d,]*)\]", text, re.M)
    wide = [(n, shape) for n, shape in adds
            if layers.get(n, "").startswith("block") and shape
            and int(np.prod([int(d) for d in shape.split(",")])) > 1]
    assert not wide, wide
