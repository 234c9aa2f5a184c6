"""Tests of the benchmark's own code, on the CPU.

Run from the repository root:
    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# four virtual CPU devices: the data-parallel cell file is rehearsed too
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import json  # noqa: E402

import pytest  # noqa: E402

DATA = BENCH / "tests" / "data"
# every reader under bench/metrics, whichever cells BENCHMARK.json holds
END_TO_END = [{"name": n, "unit": u} for n, u in (
    ("images_per_s", "images/s"), ("latency_p50_ms", "ms"),
    ("latency_p95_ms", "ms"), ("setup_s", "s"))]
PER_LAYER = [{"name": n, "unit": u} for n, u in (
    ("queue_wait_p50_ms", "ms"), ("padded_row_share", "%"),
    ("plan_call_ms.sat", "ms"), ("conv_roofline_share", "%"),
    ("matmul_roofline_share", "%"), ("device_idle_share.sat", "%"),
    ("device_idle_share.lat", "%"), ("mfu_int8", "%"))]


@pytest.fixture(scope="session")
def tiny():
    """Run the harness's measurement on the CPU at the test-only tiny
    configuration: ``tiny(cell, trace=False, **measure_kwargs)``.  The
    look for a chip is skipped; everything after it is the real run."""
    import jax

    import run

    serve_cnn = run.import_program()
    cfg = json.loads((DATA / "tiny_cnn.json").read_text())
    peak = json.loads((BENCH / "peaks.json").read_text())[
        "devices"]["TPU v5 lite"]

    def go(cell, trace=False, seed=2**31 + 11, seconds=0.5, **kw):
        wl = json.loads((DATA / f"{cell}.json").read_text())
        wl["name"] = cell
        kw.setdefault("metrics", PER_LAYER if trace else END_TO_END)
        chips = int(wl["chips"])
        return run.measure(wl, cfg, seed, seconds, trace,
                           jax.devices()[:chips],
                           serve_cnn, peak=peak, **kw)
    return go
