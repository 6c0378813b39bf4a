"""What a cell is made of, found by name.

``BENCHMARK.json`` names the cells, the configurations and the metrics;
whatever belongs to one of them sits in a file of its own:

- ``benchmark/configs/<configuration>.json`` (path given by the entry's
  ``file``) -- the published keys, ``reduced``, ``assumed``, the engine or
  optimizer settings, the deployment it stands for, the ``rehearse`` sizes
  the CPU tests run at, and the limits of its ``correct`` comparison;
- ``benchmark/traffic/<mix>.json`` -- the driver and its parameters;
- ``benchmark/end_metrics/<name>.py``, ``benchmark/layer_metrics/<name>.py``
  -- one reader per metric (the variants ``<name>.<suffix>`` of one
  quantity share ``<name>.py``): ``read(run)`` returns the number, or
  ``None`` where it finds nothing to read.

A later PR adds files and entries and edits none.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


class SpecError(SystemExit):
    """A cell, configuration, mix or metric that cannot be found."""

    def __init__(self, msg):
        super().__init__(f"benchmark: {msg}")


@dataclass
class Cell:
    name: str
    chips: int
    why: str
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list = field(default_factory=list)   # metric entries
    per_layer: list = field(default_factory=list)
    root: Path = ROOT


def _json(path: Path, what: str) -> dict:
    if not path.is_file():
        raise SpecError(f"{what}: no file {path}")
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(cell_name: str, root: Path = ROOT) -> Cell:
    bench = _json(root / "BENCHMARK.json", "the benchmark")
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in cells:
        raise SpecError(f"no workload {cell_name!r} in BENCHMARK.json "
                        f"(it has {sorted(cells)})")
    w = cells[cell_name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {cell_name!r} names configuration "
                        f"{w['config']!r}, which BENCHMARK.json lacks")
    config = _json(root / configs[w["config"]]["file"],
                   f"configuration {w['config']!r}")
    traffic = _json(root / "benchmark" / "traffic" / f"{w['traffic']}.json",
                    f"traffic mix {w['traffic']!r}")
    cell = Cell(name=cell_name, chips=int(w["chips"]), why=w["why"],
                config_name=w["config"], config=config,
                traffic_name=w["traffic"], traffic=traffic, root=root)
    cell.end_to_end = [m for m in bench["end_to_end"]
                       if _reports(m, cell_name)]
    cell.per_layer = [m for m in bench["per_layer"]
                      if _reports(m, cell_name)]
    for kind, metrics in (("end_metrics", cell.end_to_end),
                          ("layer_metrics", cell.per_layer)):
        for m in metrics:
            reader(root, kind, m["name"])   # refuse a missing reader now
    return cell


def reader(root: Path, kind: str, name: str):
    """The ``read(run)`` of ``benchmark/<kind>/<name>.py``. A quantity
    split by the end-to-end metric it moves (``step_ms.rate``,
    ``step_ms.train``) is read by ``step_ms.py`` unless a variant brings
    a file of its own."""
    path = root / "benchmark" / kind / f"{name}.py"
    if not path.is_file() and "." in name:
        path = path.with_name(f"{name.split('.', 1)[0]}.py")
    if not path.is_file():
        raise SpecError(f"metric {name!r}: no reader {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"metric {name!r}: {path} defines no read(run)")
    return mod.read


def sizes(cell: Cell, rehearse: bool) -> tuple[dict, dict]:
    """(configuration, traffic) as run: the files as they stand, or with
    their ``rehearse`` groups laid over them for the CPU tests."""
    cfg, mix = dict(cell.config), dict(cell.traffic)
    if rehearse:
        over = cfg.get("rehearse", {})
        for group in ("engine", "program", "optimizer"):
            if group in over:
                cfg[group] = {**cfg.get(group, {}), **over[group]}
        cfg.update({k: v for k, v in over.items()
                    if k not in ("engine", "program", "optimizer")})
        mix.update(mix.get("rehearse", {}))
    return cfg, mix
