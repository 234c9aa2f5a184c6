"""Logical work and the peaks table."""

import json
import pathlib

import pytest

import work

CONFIGS = pathlib.Path(work.__file__).resolve().parent / "configs"


def cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_vgg11_counts():
    c = cfg("vgg11_r4_fused")
    conv = sum(l.macs for l in work.layers(c) if l.kind == "conv")
    linear = sum(l.macs for l in work.layers(c) if l.kind == "linear")
    assert conv == 7_485_456_384
    assert linear == 119_947_264
    assert work.ops_per_image(c) == pytest.approx(15.21e9, rel=1e-3)
    assert work.ops_per_image(c, "conv") == 2 * conv


def test_lenet5_counts():
    # 6C5, 16C5, 120C5 and the 120-120-84-10 classifier of the paper's
    # LeNet-5 (the 120x120 layer included)
    c = cfg("lenet5_r4_bitserial")
    macs = [l.macs for l in work.layers(c)]
    assert macs == [117_600, 240_000, 48_000, 14_400, 10_080, 840]
    assert work.ops_per_image(c) == 861_840


def test_bytes_are_unpadded_levels_weights_and_output():
    c = cfg("vgg11_r4_fused")
    first, last = work.layers(c)[0], work.layers(c)[-1]
    assert first.in_bytes == 224 * 224 * 3
    assert first.out_bytes == 224 * 224 * 64
    assert first.weight_bytes == 3 * 3 * 3 * 64
    assert last.out_bytes == 100 * 4          # int32 logits accumulator
    assert first.bytes(2) == 2 * (224 * 224 * 67) + 1728


def test_least_time_takes_the_binding_roof():
    peak = work.peaks("TPU v5 lite")
    c = cfg("vgg11_r4_fused")
    fc1 = [l for l in work.layers(c) if l.kind == "linear"][0]
    # one image: the 102.8 MB of weights bind, not the 0.2 GOP
    assert fc1.least_time_s(1, peak) == pytest.approx(
        fc1.bytes(1) / 819e9)
    conv4 = work.layers(c)[3]
    assert conv4.least_time_s(128, peak) == pytest.approx(
        conv4.ops(128) / 393e12)


def test_peaks_table_is_keyed_by_device_kind():
    p = work.peaks("TPU v5 lite")
    assert p["int8_ops_per_s"] == 393e12
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in json.loads(work.PEAKS_FILE.read_text())["source"]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("cpu")
