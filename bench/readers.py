"""Find a metric's reader by its name: ``bench/metrics/<name>.py``, whose
``read(run)`` returns the value, or None where the run holds nothing to
read (the harness then leaves the metric out)."""

import importlib.util
import pathlib

METRICS = pathlib.Path(__file__).resolve().parent / "metrics"


def load(name: str):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_").replace("-", "_"),
        METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
