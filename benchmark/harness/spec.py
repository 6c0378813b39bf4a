"""What a cell is made of, found by name.

``BENCHMARK.json`` names the cells, the configurations and the metrics;
whatever belongs to one of them sits in a file of its own:

- ``benchmark/configs/<configuration>.json`` (path given by the entry's
  ``file``) -- the published keys, ``reduced``, ``assumed``, the engine or
  optimizer settings, the deployment it stands for, the ``rehearse`` sizes
  the CPU tests run at, and the limits of its ``correct`` comparison;
- ``benchmark/published/<model>.json`` -- the source's own ``config.json``
  (the keys that fix a shape) and the keys a cut may lower (``counts``),
  found by the ``source`` URL that the entry and the configuration file
  give; ``check_cut`` holds the file to it, so a run refuses a
  configuration whose width or constant differs from the published one;
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
    """A cell, configuration, mix or metric that cannot be found, or a
    configuration that is not its source's but for its stated cut."""

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


def published(source: str, root: Path = ROOT) -> dict:
    """The ``benchmark/published/*.json`` file whose ``source`` this is."""
    for path in sorted((root / "benchmark" / "published").glob("*.json")):
        pub = _json(path, "published keys")
        if pub.get("source") == source:
            return pub
    raise SpecError(f"no file under benchmark/published/ has the source "
                    f"{source!r}")


DEPTH = "num_hidden_layers"


def check_cut(entry: dict, cfg: dict, pub: dict) -> None:
    """A configuration file is its source's published ``config.json`` but
    for its stated cut. The keys whose value differs from the published
    one are exactly the entry's ``reduced`` and exactly the keys of the
    file's ``reduced`` group (which may be empty: a model held whole).
    Each is one of the source's ``counts`` (layers, heads, experts, rows
    of the vocabulary: never a width or a constant), a whole number with
    ``1 <= here < published``, recorded as ``{"published", "here"}``. A
    key other than depth is the chip's share of a layer and says so:
    ``"chips": n``, the chips that share each layer (one ``n`` a file),
    with ``here * n >= published > here * (n - 1)``. The guide's floors
    (a period plus four layers, 8 experts, an eighth of the vocabulary)
    are the reviewer's, not this rule's."""
    def refuse(msg):
        raise SpecError(f"configuration of {entry.get('source')!r}: {msg}")

    if not cfg.get("source") == entry.get("source") == pub["source"]:
        refuse(f"'source' is {cfg.get('source')!r} in the file and "
               f"{pub['source']!r} as published")
    keys, counts = pub["keys"], set(pub["counts"])
    missing = object()
    changed = {k for k, v in keys.items() if cfg.get(k, missing) != v}
    listed, recorded = set(entry["reduced"]), cfg.get("reduced", {})
    for k in sorted(changed - counts):
        refuse(f"{k!r} is {cfg.get(k)!r}, published {keys[k]!r}: a width "
               f"or a constant may never differ")
    for k in sorted(changed - listed):
        refuse(f"{k!r} is {cfg.get(k)!r}, published {keys[k]!r}, and the "
               f"entry's 'reduced' does not list it")
    for k in sorted(listed - changed):
        refuse(f"'reduced' lists {k!r}, which is the published "
               f"{keys.get(k)!r}")
    if set(recorded) != listed:
        refuse(f"the file's 'reduced' group has {sorted(recorded)}, the "
               f"entry's 'reduced' {sorted(listed)}")
    shares = set()
    for k in sorted(listed):
        here, was, rec = cfg[k], keys[k], recorded[k]
        if not (isinstance(here, int) and isinstance(was, int)
                and 1 <= here < was):
            refuse(f"{k!r} is {here!r}: a cut is a whole number from 1 to "
                   f"under the published {was!r}")
        if (rec.get("published"), rec.get("here")) != (was, here):
            refuse(f"'reduced' records {k!r} as {rec}, the file holds "
                   f"{here} of the published {was}")
        if k == DEPTH:
            continue
        n = rec.get("chips")
        if not (isinstance(n, int) and n >= 2):
            refuse(f"{k!r} is the chip's share of a layer: its 'reduced' "
                   f"record needs \"chips\": n, the chips (2 or more) "
                   f"that share each layer")
        if not here * n >= was > here * (n - 1):
            refuse(f"{k!r}: {here} held on each of {n} chips is not the "
                   f"published {was} divided over them")
        shares.add(n)
    if len(shares) > 1:
        refuse(f"one number of chips shares each layer, the file gives "
               f"{sorted(shares)}")
    if not (isinstance(cfg.get("deployment"), str) and cfg["deployment"]):
        refuse("'deployment' has to say what the cut stands for")


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
    entry = configs[w["config"]]
    config = _json(root / entry["file"], f"configuration {w['config']!r}")
    check_cut(entry, config, published(entry["source"], root))
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
