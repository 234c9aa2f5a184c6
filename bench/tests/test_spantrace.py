"""The reduction by the program's spans and plan modules (spantrace.py):
on synthetic events, on a CPU trace of the served path, and on the
recorded v5e trace, whose ``devtrace`` readings are pinned."""

import hashlib
import json
import pathlib

import numpy as np
import pytest

import devtrace
import readers
import run
import spantrace

TRACE = pathlib.Path(devtrace.__file__).resolve().parent / "testdata"
# the recorded trace is of the LeNet-5 configuration
KERNELS = readers.family(run.config("lenet5_r4_bitserial")).KERNELS
Ev = devtrace.Event


def Sp(name, start, end, **args):
    return spantrace.Span(name, start, end, tuple(sorted(args.items())))


def _trace(host, program, ops, modules=()):
    return {"host": host, "program": program, "devices": {0: list(ops)},
            "modules": {0: list(modules)}}


def test_idle_split_takes_the_innermost_span_of_either_kind():
    spans = [Ev("submit", 0, 100), Sp("queue.flush", 5, 95),
             Sp("queue.assemble", 10, 40), Sp("queue.infer", 40, 90),
             Ev("infer", 42, 88)]
    busy = [(45, 80)]
    assert spantrace.idle_split(busy, spans, 0, 100) == {
        "submit": 10, "queue.flush": 10, "queue.assemble": 30,
        "queue.infer": 2 + 2, "infer": 3 + 8}


def test_summary_of_two_flushes():
    host = [Ev("submit", 0, 100), Ev("infer", 40, 90, images=8),
            Ev("submit", 100, 200), Ev("infer", 150, 190, images=8)]
    program = [Sp("queue.flush", 5, 95, flush=1),
               Sp("queue.assemble", 10, 40, flush=1),
               Sp("queue.infer", 40, 90, flush=1),
               Sp("queue.flush", 105, 195, flush=2),
               Sp("queue.assemble", 110, 150, flush=2),
               Sp("queue.infer", 150, 190, flush=2)]
    ops = [Ev("convert.1", 50, 60), Ev("reduce_window.2", 60, 70),
           Ev("radix_conv2d_pallas.3", 70, 80),
           Ev("convert.1", 160, 166), Ev("reduce_window.2", 166, 180),
           Ev("radix_conv2d_pallas.3", 180, 185),
           Ev("copy.9", 185, 186)]
    modules = [Ev("jit_plan_b8(123)", 50, 80),
               Ev("jit_plan_b8(123)", 160, 186)]
    maps = {8: {"convert.1": "input", "reduce_window.2": "pool1",
                "radix_conv2d_pallas.3": "conv1"}}
    red = spantrace.summarize(_trace(host, program, ops, modules), maps)
    assert red["window_s"] == 200e-9
    assert red["span_counts"] == {"queue.flush": 2, "queue.assemble": 2,
                                  "queue.infer": 2}
    assert red["span_ms"]["queue.assemble"] == pytest.approx(35e-6)
    # idle 0-50, 80-160, 186-200: the assembles hold 30 + 40 ns of it
    assert red["idle_by_span"]["queue.assemble"] == pytest.approx(70e-9)
    assert sum(red["idle_by_span"].values()) == pytest.approx(144e-9)
    queue = sum(v for k, v in red["idle_by_span"].items()
                if k.startswith("queue.") and k != "queue.infer")
    assert red["queue_idle_share"] == pytest.approx(100 * queue / 200e-9)
    assert red["idle_gaps"][0] == ["queue.assemble", 80e-9]
    assert red["plan_calls"] == [{"input": 10, "pool1": 10, "conv1": 10},
                                 {"input": 6, "pool1": 14, "conv1": 5,
                                  "unmapped": 1}]
    assert spantrace.scope_ms(red, "pool") == pytest.approx(12e-6)
    assert spantrace.scope_ms(red, "input") == pytest.approx(8e-6)
    assert spantrace.scope_ms(red, "fc") is None


def test_module_containment_on_the_device_clock():
    """An op belongs to the plan call whose module event holds it whole;
    a module of another program, or one outside the window, is no plan
    call."""
    ops = [Ev("a.1", 10, 20), Ev("a.1", 25, 35), Ev("b.2", 35, 41),
           Ev("a.1", 60, 70), Ev("a.1", 200, 210)]
    modules = [Ev("jit_plan_b4(1)", 10, 40), Ev("jit_add(2)", 60, 70),
               Ev("jit_plan_b4(1)", 195, 215)]
    calls = spantrace.plan_calls(ops, modules, {4: {"a.1": "fc1"}}, 0, 100)
    assert calls == [{"fc1": 20}]


def test_no_program_spans_no_plan_calls():
    host = [Ev("submit", 0, 100), Ev("infer", 10, 90, images=1)]
    red = spantrace.summarize(_trace(host, [], [Ev("x.1", 20, 30)],
                                     [Ev("jit_forward(9)", 20, 30)]))
    assert red["span_ms"] == {} and red["plan_calls"] == []
    assert red["queue_idle_share"] == 0.0
    assert spantrace.scope_ms(red, "pool") is None
    assert spantrace.summarize(_trace([], [], [])) is None


def test_cpu_trace_holds_the_program_spans(tmp_path):
    """A tiny net served through the queue under a CPU profiler session:
    the loader finds the program's spans with their arguments."""
    import jax

    serve_cnn = run.import_program()
    from repro.runtime import trace

    cfg = run.config_file(pathlib.Path(__file__).parent / "data"
                          / "tiny_cnn.json")
    import netgen
    family = readers.family(cfg)
    key = netgen.root_key(5)
    weights = family.make_weights(cfg, jax.random.fold_in(key, 0))
    qnet = family.program_net(cfg, weights)
    item = tuple(cfg["input_hw"])
    server = serve_cnn.CNNServer(qnet, item, buckets=(4,), data_parallel=1)
    server.warmup()
    queue = serve_cnn.MicroBatchQueue(server, timeout_s=0.0)
    x = np.zeros((2,) + item, np.float32)
    jax.profiler.start_trace(str(tmp_path))
    try:
        ticket = queue.submit(x)
    finally:
        jax.profiler.stop_trace()
    assert ticket.ok
    program = spantrace.load(tmp_path)["program"]
    names = {s.name for s in program}
    assert names == set(trace.SPANS)
    flush = [dict(s.args) for s in program if s.name == "queue.flush"]
    assert flush == [{"flush": 1, "tickets": 1, "first_ticket": 1,
                      "last_ticket": 1, "images": 2, "trigger": "timeout"}]


@pytest.fixture(scope="module")
def chip_trace():
    files = sorted(TRACE.glob("*.xplane.pb"))
    if not files:
        pytest.fail(f"no recorded chip trace under {TRACE}")
    return files[0]


def _digest(calls) -> str:
    return hashlib.sha256(json.dumps(calls).encode()).hexdigest()[:16]


def test_devtrace_readings_of_the_chip_trace_are_pinned(chip_trace):
    """The window, busy time and kernel join of the recorded trace, as
    the reduction read them when the benchmark was accepted."""
    red = devtrace.reduce(chip_trace, 1, KERNELS)
    assert red["window_s"] == 0.249215736
    assert red["busy_s"] == 0.008075136
    conv, matmul = red["kernels"]["conv"], red["kernels"]["matmul"]
    assert (len(conv), sum(n for n, _ in conv),
            sum(sum(d) for _, d in conv), _digest(conv)) == (
        28, 50, 6997234, "aaf6252f5447a5cf")
    assert (len(matmul), sum(n for n, _ in matmul),
            sum(sum(d) for _, d in matmul), _digest(matmul)) == (
        28, 50, 70279, "fddbf43e9b1799a2")


def test_chip_trace_of_a_program_without_spans(chip_trace):
    """The recorded trace predates the program's spans: the split of its
    idle time covers exactly the window's idle time, its gaps read as
    devtrace's, and no module is a plan call (``jit_forward``)."""
    red = spantrace.reduce(chip_trace)
    dev = devtrace.reduce(chip_trace, 1, KERNELS)
    assert red["window_s"] == dev["window_s"]
    assert sum(red["idle_by_span"].values()) == pytest.approx(
        dev["window_s"] - dev["busy_s"], rel=1e-9)
    assert red["idle_gaps"] == dev["breakdown"]["idle_gaps"]
    assert red["span_ms"] == {} and red["plan_calls"] == []
    assert red["queue_idle_share"] == 0.0
