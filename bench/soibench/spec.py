"""Find a cell's files by the names in ``BENCHMARK.json``."""

from __future__ import annotations

import dataclasses
import json
import pathlib

from soibench import families

ROOT = pathlib.Path(__file__).resolve().parents[2]   # the checkout


class SpecError(RuntimeError):
    """The checkout lacks a file or an entry the cell needs."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict          # the configuration file, as run
    traffic: dict         # the traffic file
    limits: dict          # bench/limits/<cell>.json
    end_to_end: tuple     # BENCHMARK.json end_to_end entries of this cell
    per_layer: tuple      # BENCHMARK.json per_layer entries of this cell
    root: pathlib.Path = ROOT   # the checkout whose bench/ holds its files

    @property
    def family(self):
        """The family module the configuration names
        (:mod:`soibench.families`)."""
        return families.of(self.config)


def _load_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


def _config(path: pathlib.Path) -> dict:
    """A configuration file, whose family module is in the checkout."""
    config = _load_json(path)
    try:
        families.of(config)
    except (ValueError, ModuleNotFoundError) as e:
        raise SpecError(f"{path}: {e}") from e
    return config


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_pair(config: str, traffic: str, root: pathlib.Path = ROOT) -> Cell:
    """A configuration of BENCHMARK.json under a traffic mix that no cell
    pairs it with yet (to size a cell before it is added): no limit, no
    metrics."""
    bench = _load_json(root / "BENCHMARK.json")
    configs = {c["name"]: c for c in bench["configs"]}
    if config not in configs:
        raise SpecError(f"no config {config!r} in BENCHMARK.json")
    return Cell(name=f"{config}.{traffic}", config_name=config,
                traffic_name=traffic, chips=1,
                config=_config(root / configs[config]["file"]),
                traffic=_load_json(root / "bench" / "traffic"
                                   / f"{traffic}.json"),
                limits={"max_gap": None}, end_to_end=(), per_layer=(),
                root=root)


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with every file it
    names loaded."""
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name} names config {w['config']!r}, "
                        f"which BENCHMARK.json does not list")
    config = _config(root / configs[w["config"]]["file"])
    bench_dir = root / "bench"
    traffic = _load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    limits = _load_json(bench_dir / "limits" / f"{name}.json")
    return Cell(
        name=name, config_name=w["config"], traffic_name=w["traffic"],
        chips=int(w["chips"]), config=config, traffic=traffic, limits=limits,
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)),
        root=root)
