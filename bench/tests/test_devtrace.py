"""The trace reduction, on interval arithmetic and on a small trace that
was recorded on a TPU v5e (``bench/testdata``: a LeNet-5 open-loop window
of the harness, 0.25 s under the profiler)."""

import pathlib

import pytest

import devtrace
import readers
import run

TRACE = pathlib.Path(devtrace.__file__).resolve().parent / "testdata"
# the recorded trace is of the LeNet-5 configuration
KERNELS = readers.family(run.config("lenet5_r4_bitserial")).KERNELS


def test_merge_unions_overlaps():
    assert devtrace.merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3),
                                                                 (5, 9)]


def test_gaps_and_clip():
    busy = devtrace.merge(devtrace.clip([(0, 2), (4, 6), (9, 20)], 1, 10))
    assert busy == [(1, 2), (4, 6), (9, 10)]
    assert devtrace.gaps(busy, 1, 10) == [(2, 4), (6, 9)]
    assert devtrace.gaps([], 0, 5) == [(0, 5)]


def test_span_at_takes_the_innermost():
    host = [devtrace.Event("submit", 0, 100),
            devtrace.Event("infer", 10, 50, images=8),
            devtrace.Event("wait", 120, 130)]
    assert devtrace.span_at(host, 20).name == "infer"
    assert devtrace.span_at(host, 70).name == "submit"
    assert devtrace.span_at(host, 110) is None


def test_op_name_is_the_instruction_not_its_operands():
    text = ("%convert.43 = s32[8,4]{1,0} convert(u8[8,4]{1,0} "
            "%radix_conv2d_pallas.8)")
    assert devtrace.op_name(text) == "convert.43"
    assert devtrace.op_name("%radix_conv2d_pallas.8 = u8[1]{0} "
                            "custom-call()") == "radix_conv2d_pallas.8"


def test_kernel_calls_attributes_events_to_infer_spans():
    dev = {0: [devtrace.Event("radix_conv2d_pallas.1", 12, 20),
               devtrace.Event("convert.3", 20, 22),
               devtrace.Event("radix_conv2d_pallas.2", 22, 30),
               devtrace.Event("radix_conv2d_pallas.1", 60, 70)]}
    host = [devtrace.Event("infer", 10, 40, images=8)]
    conv, matmul = KERNELS["conv"], KERNELS["matmul"]
    assert devtrace.kernel_calls(dev, host, conv, tol_ns=5) == [(8, [8, 8])]
    assert devtrace.kernel_calls(dev, host, matmul, tol_ns=5) == []
    # within the clock tolerance, the late event joins the call too
    assert devtrace.kernel_calls(dev, host, conv, tol_ns=30) == [
        (8, [8, 8, 10])]


@pytest.fixture(scope="module")
def chip_trace():
    files = sorted(TRACE.glob("*.xplane.pb"))
    if not files:
        pytest.fail(f"no recorded chip trace under {TRACE}")
    return files[0]


def test_chip_trace_has_device_ops_and_host_spans(chip_trace):
    tr = devtrace.load(chip_trace)
    assert 0 in tr["devices"] and len(tr["devices"][0]) > 0
    names = {h.name for h in tr["host"]}
    assert {"submit", "infer"} <= names
    assert all(h.images > 0 for h in tr["host"] if h.name == "infer")


def test_chip_trace_reduces(chip_trace):
    red = devtrace.reduce(chip_trace, 1, KERNELS)
    assert 0 < red["busy_s"] < red["window_s"]
    ops = red["breakdown"]["device_ops"]
    gaps = red["breakdown"]["idle_gaps"]
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    assert all(t > 0 for _, t in ops + gaps)
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    calls = red["kernels"]["conv"]
    assert calls and all(len(d) == 3 for _, d in calls)   # LeNet: 3 convs
    assert all(len(d) == 3 for _, d in red["kernels"]["matmul"])
