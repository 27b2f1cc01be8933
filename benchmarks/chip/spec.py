"""Find a cell's parts by name.

``BENCHMARK.json`` names the cells; each part of a cell sits in a file
of its own that this module finds by that name, so that a later change
adds a configuration, a traffic mix or a per-layer metric by adding
files and entries, with no edit to a file that is here:

  configs/<config>.json      the configuration, as run (its entry's "file")
  references/<arch>.py       the plain reference the configuration names
  traffic/<traffic>.json     the traffic mix, read by traffic.py
  metrics/<metric>.py        a per-layer metric's reader: read(ctx)
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))


class SpecError(ValueError):
    pass


@dataclass
class Metric:
    name: str
    unit: str
    read: Optional[Callable] = None    # per-layer only: read(ctx)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[Metric] = field(default_factory=list)
    per_layer: List[Metric] = field(default_factory=list)


def load_module(path: str, name: str):
    """Import the Python file at ``path`` under ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise SpecError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing {path}") from None


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(bench_path: str, workload: str, here: str = HERE) -> Cell:
    """The cell ``workload`` of the benchmark at ``bench_path``, with
    its configuration, traffic and the metrics it reports."""
    bench = _json(bench_path)
    root = os.path.dirname(os.path.abspath(bench_path))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in {bench_path}; have "
                        f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[w["config"]]
    config = _json(os.path.join(root, entry["file"]))
    if config.get("name") != entry["name"]:
        raise SpecError(f"{entry['file']} names {config.get('name')!r}, "
                        f"not {entry['name']!r}")
    traffic = _json(os.path.join(here, "traffic", w["traffic"] + ".json"))
    cell = Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic)
    for m in bench["end_to_end"]:
        if _applies(m, workload):
            cell.end_to_end.append(Metric(m["name"], m["unit"]))
    for m in bench["per_layer"]:
        if _applies(m, workload):
            path = os.path.join(here, "metrics", m["name"] + ".py")
            mod = load_module(path, "metric_" + m["name"].replace(".", "_")
                              .replace("-", "_"))
            cell.per_layer.append(Metric(m["name"], m["unit"], mod.read))
    return cell


def reference(arch: str, here: str = HERE):
    """The plain reference module of ``arch``."""
    return load_module(os.path.join(here, "references", arch + ".py"),
                       "reference_" + arch.replace(".", "_")
                       .replace("-", "_"))


def peaks(device_kind: str, here: str = HERE) -> Dict[str, float]:
    """The published peaks of ``device_kind``; a device missing from the
    table is an error, never a default."""
    table = _json(os.path.join(here, "peaks.json"))["devices"]
    if device_kind not in table:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        f"peaks.json (have {sorted(table)})")
    return table[device_kind]
