"""Where a cell's parts live, found by the names in ``BENCHMARK.json``.

Everything that belongs to one deployment, one traffic mix or one
per-layer metric is a file of its own under the benchmark's directory,
named after it:

* ``chipbench/configs/<config>.json`` -- the deployment (named by the
  config entry's ``file``), which names its ``driver`` and ``problem``;
* ``chipbench/traffic/<traffic>.json`` -- the traffic mix: parameters
  that the driver's general generator reads;
* ``chipbench/metrics/<metric>.py`` -- the reader of one per-layer
  metric, a ``read(record)`` that returns a number or ``None``;
* ``chipbench/drivers/<driver>.py`` and ``chipbench/problems/
  <problem>.py`` -- the code that drives a kind of deployment and the
  problem it integrates (with its plain reference).

So a new cell made of a new configuration, traffic mix or metric adds
files and a ``BENCHMARK.json`` entry, and edits none.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import List

BENCH_DIR = "chipbench"
PACKAGE_DIR = Path(__file__).resolve().parent


class LayoutError(RuntimeError):
    """``BENCHMARK.json`` does not name what a run needs."""


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[dict] = field(default_factory=list)
    per_layer: List[dict] = field(default_factory=list)
    root: Path = Path(".")


def _load_json(path: Path) -> dict:
    try:
        with open(path) as fh:
            return json.load(fh)
    except FileNotFoundError as exc:
        raise LayoutError(f"missing file {path}") from exc


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(root: Path, name: str) -> Cell:
    """The workload ``name`` of ``<root>/BENCHMARK.json``, with its
    configuration and traffic loaded and the metrics it reports."""
    root = Path(root)
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise LayoutError(f"no workload {name!r} in BENCHMARK.json; "
                          f"have {sorted(cells)}")
    wl = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if wl["config"] not in configs:
        raise LayoutError(f"workload {name!r} names unknown config "
                          f"{wl['config']!r}")
    config = _load_json(root / configs[wl["config"]]["file"])
    traffic = _load_json(_find(root, "traffic", f"{wl['traffic']}.json"))
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    # a per-layer metric without a workloads list belongs to every cell
    # that reports the end-to-end metric it moves
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in reported)]
    return Cell(name=name, chips=int(wl["chips"]), config_name=wl["config"],
                config=config, traffic_name=wl["traffic"], traffic=traffic,
                end_to_end=e2e, per_layer=layer, root=root)


def _find(root: Path, kind: str, filename: str) -> Path:
    """``<root>/chipbench/<kind>/<filename>``, else the same file in this
    package (a checkout's root and this package's are one directory;
    a test's scratch root holds only the files it adds)."""
    for base in (Path(root) / BENCH_DIR, PACKAGE_DIR):
        path = base / kind / filename
        if path.is_file():
            return path
    raise LayoutError(f"missing file {BENCH_DIR}/{kind}/{filename}")


def _module_from(path: Path, modname: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(root: Path, name: str) -> ModuleType:
    """The reader module of per-layer metric ``name``."""
    return _module_from(_find(root, "metrics", f"{name}.py"),
                        f"chipbench_metric_{name.replace('.', '_')}")


def driver(root: Path, name: str) -> ModuleType:
    return _module_from(_find(root, "drivers", f"{name}.py"),
                        f"chipbench_driver_{name}")


def problem(root: Path, name: str) -> ModuleType:
    return _module_from(_find(root, "problems", f"{name}.py"),
                        f"chipbench_problem_{name}")
