"""The harness end to end on the CPU, at the tiny test-only size."""

import json

import pytest

import run


@pytest.mark.parametrize("cell", ["tiny-closed", "tiny-poisson"])
def test_untraced_run(tiny, cell):
    res = tiny(cell)
    assert json.loads(json.dumps(res)) == res
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    m = res["metrics"]
    assert m["setup_s"]["value"] > 0 and m["setup_s"]["unit"] == "s"
    assert m["images_per_s"]["value"] > 0
    assert 0 < m["latency_p50_ms"]["value"] <= m["latency_p95_ms"]["value"]
    assert res["device"]["platform"] == "cpu"
    c = res["checks"]["mismatched_logits"]
    assert c["value"] == 0 and c["limit"] == 0 and c["compared"] >= 10


def test_traced_run_reads_host_metrics(tiny):
    res = tiny("tiny-poisson", trace=True)
    m = res["metrics"]
    assert res["correct"] is True
    assert 0 <= m["padded_row_share"]["value"] < 100
    assert m["queue_wait_p50_ms"]["value"] >= 0
    assert m["plan_call_ms.sat"]["value"] > 0
    # a CPU trace has no TPU plane: device metrics stay out, never 0
    assert "device_idle_share.lat" not in m
    assert "conv_roofline_share" not in m


def test_same_seed_same_requests(tiny):
    a = tiny("tiny-poisson", seed=5)
    b = tiny("tiny-poisson", seed=5)
    assert a["attempted"] == b["attempted"]


def test_measurement_path_refuses_the_cpu(capsys):
    assert run.main(["--workload", "vgg11-saturate", "--seed", "1",
                     "--seconds", "1"]) == 1
    out = capsys.readouterr()
    assert out.out == "" and "needs a TPU" in out.err


def test_cell_metrics_follow_benchmark_json():
    names = [m["name"] for m in run.cell_metrics("vgg11-saturate", False)]
    assert names == ["images_per_s", "setup_s"]
    names = [m["name"] for m in run.cell_metrics("vgg11-saturate", True)]
    assert "mfu_int8" in names and "queue_wait_p50_ms" not in names


def test_every_metric_in_benchmark_json_has_a_reader():
    import json

    import readers

    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(readers.load(m["name"]))
    cells = {w["name"] for w in spec["workloads"]}
    for w in cells:
        assert run.config(run.workload(w)["config"])


def test_data_parallel_cell_on_four_virtual_devices(tiny):
    res = tiny("tiny-dp4-closed")
    assert res["device"]["count"] == 4
    assert res["correct"] is True and res["failed"] == 0
    assert res["metrics"]["images_per_s"]["value"] > 0


def test_data_parallel_workload_file_matches_its_one_chip_twin():
    one = run.workload("vgg11-saturate")
    four = run.workload("vgg11-dp4-saturate")
    assert four["chips"] == 4 and four["buckets"] == [4 * one["buckets"][0]]
    assert four["clients"] == 4 * one["clients"]
    assert four["config"] == one["config"]
