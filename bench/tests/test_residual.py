"""The residual family (ResNet-34): its logical work, the float32 bound
of its joins on seeded weights, and a tiny residual configuration run
through the harness on the CPU."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import netgen
import readers
import run
import work
from conftest import DATA

CONFIGS = run.BENCH / "configs"


def cfg(name):
    return run.config_file(CONFIGS / f"{name}.json")


def test_resnet34_counts():
    """3.664 GMAC per image (Table 1's 3.6e9), split by kernel: the 16
    joined conv_b, the stem with the 16 conv_a and 3 projections, and the
    512 -> 1000 classifier."""
    c = cfg("resnet34_r4_fused")
    lays = work.layers(c)
    assert [l.kernel for l in lays].count("conv") == 20
    assert [l.kernel for l in lays].count("conv_res") == 16
    assert [l.kernel for l in lays].count("matmul") == 1
    assert len(lays) == 37
    assert work.ops_per_image(c) == 7_327_522_816
    assert work.ops_per_image(c, "conv_res") == 3_699_376_128
    assert work.ops_per_image(c, "conv") == 3_627_122_688
    assert work.ops_per_image(c, "matmul") == 1_024_000
    assert sum(l.weight_bytes for l in lays) == 21_779_648


def test_resnet34_join_bytes_hold_the_skip_operands():
    """A join's input bytes are conv_a's levels plus the skip: uint8
    identities and int32 projections, 1,906,688 bytes per image."""
    lays = work.layers(cfg("resnet34_r4_fused"))
    skip = sum(l.in_bytes - l.out_bytes for l in lays
               if l.kernel == "conv_res")
    assert skip == 1_906_688
    first = lays[0]
    assert first.in_bytes == 224 * 224 * 3
    assert first.out_bytes == 112 * 112 * 64
    assert lays[-1].in_bytes == 512 * 4        # the 10-bit pool carry


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_resnet34_joins_stay_exact_in_float32(seed):
    """Every integer a join can form, over every input, stays below
    2^24 on the seeded weights, so the float32 reference is exact."""
    c = cfg("resnet34_r4_fused")
    family = readers.family(c)
    w = family.make_weights(c, jax.random.fold_in(netgen.root_key(seed), 0))
    bound = family.join_bound(c, w)
    assert 0 < bound < 2**24
    assert all(int(p["res_scale"].min()) >= 1 for p in w["blocks"])
    # a row per channel, not one number repeated: a misindexed row shows
    assert all(len(set(np.asarray(p["res_scale"]).tolist())) > 1
               for p in w["blocks"])


@pytest.mark.parametrize("shape,span", [((2, 7, 7, 64), 300),
                                        ((1000,), 5), ((3, 11, 13), 1 << 20),
                                        ((4097,), 0)])
def test_calibration_percentile_is_the_percentile(shape, span):
    """The bisection percentile is numpy's (linear interpolation between
    the two order statistics, in float32) on integer-valued arrays, ties
    and a constant array included; the extremes are exact."""
    family = readers.family(run.config_file(DATA / "tiny_resnet.json"))
    x = np.random.default_rng(span).integers(-span, span + 1, shape)
    got = {pct: float(family._percentile(jnp.asarray(x, jnp.float32), pct))
           for pct in (99.9, 50.0, 0.0, 100.0)}
    for pct, value in got.items():
        np.testing.assert_allclose(value, np.percentile(x, pct), rtol=1e-6)
    assert (got[0.0], got[100.0]) == (x.min(), x.max())


def test_blocks_of_one_shape_share_one_weight_program():
    """The weights of blocks of one shape are drawn by one compiled
    program: 5 blocks in 3 shapes compile 3 (ResNet-34: 16 in 8)."""
    c = run.config_file(DATA / "tiny_resnet.json")
    c = {**c, "stages": [{"out": 8, "blocks": 3, "stride": 1},
                         {"out": 16, "blocks": 2, "stride": 2}]}
    family = readers.family(c)
    family._block_weights.clear_cache()
    w = family.make_weights(c, netgen.root_key(3))
    assert len(w["blocks"]) == 5
    assert family._block_weights._cache_size() == 3


def test_tiny_residual_configuration_is_correct(tiny):
    res = tiny("tiny-resnet-closed")
    assert res["correct"] is True and res["failed"] == 0
    assert res["checks"]["mismatched_logits"]["compared"] >= 16 * 10


def test_a_reference_with_the_wrong_res_scale_is_not_correct(tiny,
                                                             monkeypatch):
    family = readers.family(run.config_file(DATA / "tiny_resnet.json"))
    make = family.make_forward

    def off_by_one(cfg, dtype):
        fwd = make(cfg, dtype)

        def wrong(weights, x):
            blocks = [{**p, "res_scale": p["res_scale"] + 1}
                      for p in weights["blocks"]]
            return fwd({**weights, "blocks": blocks}, x)
        return wrong
    monkeypatch.setattr(family, "make_forward", off_by_one)
    res = tiny("tiny-resnet-closed")
    assert res["correct"] is False
    assert res["checks"]["mismatched_logits"]["value"] > 0


def test_the_cell_names_its_configuration_and_one_chip():
    wl = json.loads((run.BENCH / "workloads" / "resnet34-saturate.json")
                    .read_text())
    assert wl["config"] == "resnet34_r4_fused" and wl["chips"] == 1
    assert wl["buckets"] == [256] and wl["clients"] * wl["sizes"]["max"] \
        == 256
