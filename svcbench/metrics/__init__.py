"""Per-layer metric readers, one file per metric, named as in
``BENCHMARK.json`` (``svcbench/metrics/<metric>.py``).

Each defines ``read(ctx) -> float | None`` over the traced run's context
(``svcbench.harness.ReadContext``): the obs spans of the window, the
metrics registry before and after it, the device-trace reduction and the
logical work of the traced calls.  A reader that finds nothing to read
returns None, and the metric is left out of the result line.
"""

from __future__ import annotations

import importlib.util
import pathlib

HERE = pathlib.Path(__file__).resolve().parent


def reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"svcbench_metric_{name.replace('.', '_')}", HERE / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
