"""The benchmark's declaration, and everything it names, found by file name.

``BENCHMARK.json`` lists configurations, cells and metrics.  Each of them has
files of its own under ``bench/``, found from its name alone, so that a later
change adds a cell or a metric by adding files:

- a configuration: ``configs/<config>.json`` (its ``file`` entry);
- a traffic mix: ``traffic/<traffic>.json``;
- a cell's correctness limits: ``limits/<cell>.json``;
- a per-layer metric: ``metrics/<metric>.py``, whose ``read(m)`` returns a
  number, or None where the run gave it nothing to read.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: dict
    limits: dict
    chips: int
    end_to_end: tuple      # metric entries this cell reports with --trace 0
    per_layer: tuple       # metric entries this cell reports with --trace 1


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load(root: Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration, traffic and limits loaded."""
    spec = load(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    bench = root / "bench"
    return Cell(
        name=name,
        config=_json(root / configs[w["config"]]["file"]),
        traffic=_json(bench / "traffic" / f"{w['traffic']}.json"),
        limits=_json(bench / "limits" / f"{name}.json"),
        chips=int(w["chips"]),
        end_to_end=tuple(m for m in spec["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in spec["per_layer"] if _applies(m, name)))


def unlisted_cell(name: str, root: Path = ROOT) -> Cell:
    """A cell that ``BENCHMARK.json`` does not list, from the files of its
    configuration and traffic (``<config>.<traffic>``), for rehearsals and
    tests: it has no limits and no metrics, and takes the chips of its
    traffic's mesh."""
    config, traffic = name.rsplit(".", 1)
    bench = root / "bench"
    t = _json(bench / "traffic" / f"{traffic}.json")
    mesh = t["mesh"] or {"data": 1, "mx": 1, "my": 1}
    return Cell(name=name, config=_json(bench / "configs" / f"{config}.json"),
                traffic=t, limits={}, chips=mesh["data"] * mesh["mx"]
                * mesh["my"], end_to_end=(), per_layer=())


def metric_reader(name: str, root: Path = ROOT):
    """``read`` of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
