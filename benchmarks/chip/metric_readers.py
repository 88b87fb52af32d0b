"""Per-layer metrics: one reader per metric, ``metrics/<name>.py``.

Each reader defines ``read(run) -> float | None`` over the run's record
(`runner.Run`): the proxy's spans and counts in the window, and, in a
traced run, the reduced trace.  A reader that finds nothing to read returns
None and the metric is left out of the result line.
"""
from __future__ import annotations

import importlib.util
import pathlib
from typing import Dict, List

HERE = pathlib.Path(__file__).resolve().parent / "metrics"


def load(name: str):
    path = HERE / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_all(specs: List[Dict], cell: str, run) -> Dict[str, Dict]:
    out = {}
    for spec in specs:
        if cell not in spec.get("workloads", [cell]):
            continue
        value = load(spec["name"]).read(run)
        if value is not None:
            out[spec["name"]] = {"value": float(value), "unit": spec["unit"]}
    return out
