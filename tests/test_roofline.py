"""Roofline report: HW constants, term/bottleneck selection, int8 peak.

Drives launch/roofline.py with a fake ``Compiled`` whose ``as_text()``
is a hand-written HLO module with exactly one dot and one all-gather,
so every roofline term is hand-computable:

    dot   f32[8,4] @ f32[4,16]  -> 2*8*16*4      = 1024 FLOPs
    bytes dot 128+256+512 + all-gather 128+128   = 1152 B
    link  all-gather over g=4 of 128 B local     = 3*128 = 384 B
"""

import dataclasses

import pytest

from repro.launch import roofline as RL

_HLO = """\
HloModule fake_cell, num_partitions=4

ENTRY %main (p0: f32[8,4], p1: f32[4,16]) -> f32[8,16] {
  %a = f32[8,4]{1,0} parameter(0)
  %b = f32[4,16]{1,0} parameter(1)
  %ag = f32[8,4]{1,0} all-gather(%a), replica_groups=[1,4], dimensions={0}
  ROOT %out = f32[8,16]{1,0} dot(%ag, %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""

FLOPS = 2 * 8 * 16 * 4          # 1024
BYTES = (128 + 256 + 512) + (128 + 128)
LINK = 3 * 128


class FakeCompiled:
    """Duck-typed jax ``Compiled``: as_text / cost_analysis / memory_analysis."""

    def __init__(self, hlo=_HLO, ca=None, mem=None):
        self._hlo, self._ca, self._mem = hlo, ca, mem

    def as_text(self):
        return self._hlo

    def cost_analysis(self):
        return self._ca if self._ca is not None else {}

    def memory_analysis(self):
        return self._mem


def _report(hw=None, **kw):
    kw.setdefault("compiled", FakeCompiled())
    kw.setdefault("model_flops", 512.0)
    if hw is not None:
        kw["hw"] = hw
    return RL.roofline("fake_arch", "train", "1x4", 4, **kw)


def test_hw_constants_int8_doubles_bf16():
    hw = RL.HW()
    assert hw.peak_flops == 197e12
    assert hw.peak_flops_int8 == 2 * hw.peak_flops
    assert hw.hbm_bw == 819e9
    assert hw.link_bw == 50e9
    # frozen: the constants are not mutable state
    with pytest.raises(dataclasses.FrozenInstanceError):
        hw.peak_flops = 1.0


def test_terms_hand_computed():
    r = _report()
    assert r.device_flops == FLOPS
    assert r.device_bytes == BYTES
    assert r.device_link_bytes == LINK
    assert r.t_compute == pytest.approx(FLOPS / 197e12)
    assert r.t_memory == pytest.approx(BYTES / 819e9)
    assert r.t_collective == pytest.approx(LINK / 50e9)
    assert r.per_collective == {"all-gather": LINK}
    # model_flops=512 over 4 chips of 1024 device flops
    assert r.useful_ratio == pytest.approx(512.0 / (4 * FLOPS))
    assert r.int8 is False


@pytest.mark.parametrize("hw,expect", [
    (RL.HW(peak_flops=1.0), "compute"),       # 1024 s compute term
    (RL.HW(hbm_bw=1.0), "memory"),            # 1152 s memory term
    (RL.HW(), "collective"),                  # real ratios: link slowest
])
def test_bottleneck_selection(hw, expect):
    r = _report(hw=hw)
    assert r.bottleneck == expect
    assert r.step_time_lb == max(r.t_compute, r.t_memory, r.t_collective)
    assert r.roofline_fraction == pytest.approx(r.t_compute / r.step_time_lb)


def test_int8_peak_halves_compute_term():
    bf16 = _report()
    i8 = _report(int8=True)
    assert i8.int8 is True
    assert i8.t_compute == pytest.approx(bf16.t_compute / 2)
    # only the compute term moves
    assert i8.t_memory == bf16.t_memory
    assert i8.t_collective == bf16.t_collective
    assert i8.to_dict()["int8"] is True


def test_raw_cost_analysis_passthrough():
    r = _report(compiled=FakeCompiled(
        ca={"flops": 999.0, "bytes accessed": 888.0}))
    assert r.raw_flops == 999.0
    assert r.raw_bytes == 888.0
    # a cost analysis without the bytes entry leaves raw_bytes unset
    r2 = _report(compiled=FakeCompiled(ca={"flops": 7.0}))
    assert r2.raw_flops == 7.0
    assert r2.raw_bytes is None


def test_memory_analysis_optional():
    assert _report().memory_per_device is None

    class Mem:
        argument_size_in_bytes = 100
        output_size_in_bytes = 20
        temp_size_in_bytes = 3
        alias_size_in_bytes = 0

    r = _report(compiled=FakeCompiled(mem=Mem()))
    assert r.memory_per_device == dict(argument_bytes=100, output_bytes=20,
                                       temp_bytes=3, alias_bytes=0)


def test_to_dict_carries_derived_fields():
    d = _report().to_dict()
    assert d["step_time_lb"] == pytest.approx(LINK / 50e9)
    assert d["arch"] == "fake_arch" and d["chips"] == 4
    assert set(d) >= {"t_compute", "t_memory", "t_collective",
                      "bottleneck", "roofline_fraction", "int8"}


def test_format_row_contents():
    row = RL.format_row(_report())
    assert "fake_arch" in row and "train" in row and "1x4" in row
    assert "collective" in row            # the bottleneck label
    assert "roofline_frac" in row and "useful" in row
