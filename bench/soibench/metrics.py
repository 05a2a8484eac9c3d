"""Metric readers, found by name: ``bench/metrics/<name>.py`` defines
``read(run)`` and returns the metric's value, or None where the run holds
nothing to read (the metric is then left out of the result line)."""

from __future__ import annotations

import importlib.util
import pathlib

from soibench.spec import ROOT


def reader(name: str, root: pathlib.Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader bench/metrics/{name}.py for "
                                f"metric {name}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_all(entries, run) -> dict:
    """``{name: {"value", "unit"}}`` of every entry whose reader found
    something."""
    out = {}
    for m in entries:
        value = reader(m["name"], run.cell.root)(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
