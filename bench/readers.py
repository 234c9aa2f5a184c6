"""Find the harness's pluggable parts by name, each a file of its own:

* a metric's reader, ``bench/metrics/<name>.py``, whose ``read(run)``
  returns the value, or None where the run holds nothing to read (the
  harness then leaves the metric out);
* a configuration's model family, ``bench/families/<family>.py``, named
  by the configuration's required ``"family"`` key.  A family provides
  everything of the harness that depends on the model:

  - ``make_weights(cfg, key)``: the seeded served arrays, made on the
    device in one jitted call;
  - ``program_net(cfg, weights)``: the object the program's server takes
    (a ``QuantizedNet``);
  - ``make_forward(cfg, dtype=jnp.float32)``: the jitted plain reference
    ``(weights, x) -> logits``, independent of the program;
    ``dtype=jnp.bfloat16`` is the control;
  - ``layers(cfg)``: the ``work.LayerWork`` list, in the order the plan
    calls its kernels, each entry naming the kernel it runs on;
  - ``KERNELS``: kernel name -> the Pallas entry point whose device
    events the trace reduction collects (``devtrace.reduce``).
"""

import functools
import importlib.util
import pathlib

BENCH = pathlib.Path(__file__).resolve().parent
METRICS = BENCH / "metrics"
FAMILIES = BENCH / "families"
FAMILY_NAMES = ("make_weights", "program_net", "make_forward", "layers",
                "KERNELS")


@functools.lru_cache(maxsize=None)
def _module(path: pathlib.Path, prefix: str):
    spec = importlib.util.spec_from_file_location(
        prefix + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load(name: str):
    return _module(METRICS / f"{name}.py", "metric_").read


def family(cfg: dict, where: str = ""):
    """The family module of ``cfg``; raises, naming ``where`` (the
    configuration's file) or the configuration's name, where the key is
    missing, its file is not there or lacks one of ``FAMILY_NAMES``."""
    where = where or f"configuration {cfg.get('name')!r}"
    name = cfg.get("family")
    if not name:
        raise ValueError(f'{where}: no "family" key naming its model family '
                         f"(a file under {FAMILIES})")
    path = FAMILIES / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"{where}: family {name!r} has no file {path}")
    mod = _module(path, "family_")
    missing = [n for n in FAMILY_NAMES if not hasattr(mod, n)]
    if missing:
        raise ValueError(f"{where}: family file {path} lacks {missing}")
    return mod
