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

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.autotune import KernelConfig
from repro.kernels.radix_attn import radix_decode_attn_pallas
from repro.kernels.radix_conv import radix_conv2d_pallas
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
          plane_parallel=False):
    """A conv layer as the plan builds it: SAME-padded input of side ``h``,
    Cout tiled by the default ``bco``, occupancy prepass on."""
    _, bco = ops._block(cout, pref=DEFAULT.bco)

    def fn(x, w, bias, mult, occ):
        return radix_conv2d_pallas(
            x, w, num_steps=T, method=method, bco=bco,
            bias=bias if epilogue else None, mult=mult if epilogue else None,
            occupancy=occ, mxu_dtype=DEFAULT.mxu_dtype,
            plane_parallel=plane_parallel)

    return fn, [((1, h, h, cin), jnp.uint8), ((k, k, cin, cout), jnp.int8),
                ((1, cout), jnp.int32), ((1, cout), jnp.float32),
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
