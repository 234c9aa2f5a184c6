"""The model-family seam: a configuration names its family, the family
file brings everything that depends on the model, and the harness finds
it by that name alone."""

import json
import pathlib
import re
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import devtrace
import netgen
import readers
import run
from conftest import DATA

TEST_FAMILIES = DATA / "families"


@pytest.fixture
def mlp(monkeypatch):
    """The test-only ``mlp`` family (``data/families/mlp.py``), with the
    harness's families directory pointed there; its call counts start
    from nothing."""
    monkeypatch.setattr(readers, "FAMILIES", TEST_FAMILIES)
    family = readers.family(run.config_file(DATA / "tiny_mlp.json"))
    family.CALLS.clear()
    return family


def test_new_family_runs_through_the_harness(tiny, mlp, monkeypatch):
    """One family file, its configuration and a workload, all new files,
    run through ``run.measure``: the family's weights, program net,
    reference, work list and kernels are the ones used."""
    kernels = []
    reduce = devtrace.reduce

    def spy(path, n_devices, kern):
        kernels.append(kern)
        return reduce(path, n_devices, kern)
    monkeypatch.setattr(devtrace, "reduce", spy)

    res = tiny("tiny-mlp-closed")
    assert res["correct"] is True and res["failed"] == 0
    assert res["checks"]["mismatched_logits"]["compared"] >= 16 * 6
    assert mlp.CALLS["make_weights"] == 1 and mlp.CALLS["program_net"] == 1
    assert mlp.CALLS["make_forward"] == 1

    res = tiny("tiny-mlp-closed", trace=True)
    assert res["correct"] is True
    assert kernels == [mlp.KERNELS]
    assert mlp.CALLS["layers"] >= 1            # mfu_int8's work per image
    assert res["metrics"]["mfu_int8"]["value"] > 0
    # a CPU trace has no TPU plane, so no kernel share is read
    assert "matmul_roofline_share" not in res["metrics"]


def test_wrong_reference_from_the_family_is_not_correct(tiny, mlp,
                                                        monkeypatch):
    make = mlp.make_forward

    def off_by_one(cfg, dtype):
        fwd = make(cfg, dtype)
        return lambda w, x: fwd(w, x).at[:, 0].add(1.0)
    monkeypatch.setattr(mlp, "make_forward", off_by_one)
    res = tiny("tiny-mlp-closed")
    assert res["correct"] is False
    assert res["checks"]["mismatched_logits"]["value"] > 0


def test_the_work_of_a_family_sets_ops_per_image(mlp):
    import work

    cfg = run.config_file(DATA / "tiny_mlp.json")
    assert [l.kernel for l in work.layers(cfg)] == ["matmul", "matmul"]
    assert work.ops_per_image(cfg) == 2 * (32 * 24 + 24 * 6)
    assert work.ops_per_image(cfg, "conv") == 0


@pytest.mark.parametrize("family", [None, "no_such_family"],
                         ids=["no_family_key", "missing_family_file"])
def test_config_without_its_family_stops_the_run(tmp_path, monkeypatch,
                                                 family):
    """No fallback to ``sequential``: the run stops, naming the file."""
    cfg = json.loads((DATA / "tiny_cnn.json").read_text())
    del cfg["family"]
    if family:
        cfg["family"] = family
    for sub, name, body in (("configs", "orphan.json", cfg),
                            ("workloads", "orphan-closed.json",
                             {**json.loads((DATA / "tiny-closed.json")
                                           .read_text()),
                              "config": "orphan"})):
        (tmp_path / sub).mkdir()
        (tmp_path / sub / name).write_text(json.dumps(body))
    monkeypatch.setattr(run, "BENCH", tmp_path)
    path = str(tmp_path / "configs" / "orphan.json")
    with pytest.raises(ValueError, match=path) as err:
        run.main(["--workload", "orphan-closed", "--seed", "1",
                  "--seconds", "1"])
    assert ("no_such_family" in str(err.value)) == bool(family)


def test_a_family_file_lacking_a_name_is_refused(tmp_path, monkeypatch):
    (tmp_path / "half.py").write_text("KERNELS = {}\n")
    monkeypatch.setattr(readers, "FAMILIES", tmp_path)
    with pytest.raises(ValueError, match="make_weights"):
        readers.family({"name": "x", "family": "half"})


CONFIGS = sorted((run.BENCH / "configs").glob("*.json")) + sorted(
    p for p in DATA.glob("*.json")
    if "family" in json.loads(p.read_text()))


def family_of(path, monkeypatch):
    """The family of the configuration at ``path``: the harness's, or a
    test-only one under ``data/families``."""
    cfg = json.loads(path.read_text())
    if not (readers.FAMILIES / f"{cfg.get('family')}.py").is_file():
        monkeypatch.setattr(readers, "FAMILIES", TEST_FAMILIES)
    return run.config_file(path), readers.family(cfg)


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_every_configuration_names_its_family(path, monkeypatch):
    """Whatever family a configuration names, its file is there with
    every name the harness calls (``run.config_file`` refuses it
    otherwise)."""
    cfg, _ = family_of(path, monkeypatch)
    assert isinstance(cfg["family"], str) and cfg["family"]


@pytest.mark.parametrize("path", [
    run.BENCH / "configs" / "vgg11_r4_fused.json",
    run.BENCH / "configs" / "lenet5_r4_bitserial.json",
    DATA / "tiny_cnn.json"], ids=lambda p: p.stem)
def test_the_first_configurations_are_sequential(path):
    assert run.config_file(path)["family"] == "sequential"


class Barred:
    """Stands in for a program module bound in a family's namespace: any
    use of it fails."""

    def __init__(self, name):
        self.name = name

    def __getattr__(self, attr):
        raise AssertionError(f"the reference uses the program: "
                             f"{self.name}.{attr}")

    def __call__(self, *a, **kw):
        raise AssertionError(f"the reference calls the program: {self.name}")


def _of_program(value) -> bool:
    name = (value.__name__ if isinstance(value, types.ModuleType)
            else getattr(value, "__module__", None) or "")
    return name == "repro" or name.startswith("repro.")


@pytest.mark.parametrize("path", [
    p for p in CONFIGS if p.parent == DATA
    or p.name == "lenet5_r4_bitserial.json"], ids=lambda p: p.stem)
def test_the_reference_imports_nothing_of_the_program(path, monkeypatch):
    """The plain reference that decides ``correct`` runs with the
    program unimportable and every program name in the family's
    namespace barred, and gives the logits it gives otherwise."""
    cfg, family = family_of(path, monkeypatch)
    key = netgen.root_key(5)
    w = family.make_weights(cfg, jax.random.fold_in(key, 0))
    x = netgen.images(cfg, jax.random.fold_in(key, 1), 4)
    want = np.asarray(family.make_forward(cfg)(w, x))
    for name in [m for m in sys.modules
                 if m == "repro" or m.startswith("repro.")]:
        monkeypatch.setitem(sys.modules, name, None)
    for name, value in list(vars(family).items()):
        if _of_program(value):
            monkeypatch.setattr(family, name, Barred(name))
    for dtype in (jnp.float32, jnp.bfloat16):
        got = np.asarray(family.make_forward(cfg, dtype)(w, x))
        assert got.shape == want.shape and np.isfinite(got).all()
    np.testing.assert_array_equal(
        np.asarray(family.make_forward(cfg)(w, x)), want)


_BRANCH = re.compile(r"""(\[\s*|\.get\(\s*)["'](kind|layers)["']""")


def test_generic_code_reads_no_layer_list():
    """Layer kinds belong to the families: no file of the harness outside
    ``bench/families`` reads a configuration's layer list or a layer's
    kind, the reads a branch on a layer kind needs.  (Names of plan
    scopes, such as a reader's ``pool`` prefix, are not such a read.)"""
    bench = pathlib.Path(run.__file__).resolve().parent
    for f in sorted(bench.glob("*.py")) + sorted(bench.glob("metrics/*.py")):
        hits = _BRANCH.findall(f.read_text())
        assert not hits, (f.name, hits)
