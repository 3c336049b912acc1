"""What one cell is made of, found by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix and
lists the metrics.  Each of these lives in a file of its own, so a cell,
a configuration, a mix or a metric is added by adding files and entries:

    bench/configs/<config>.json     sizes, serving settings, check limits
    bench/traffic/<traffic>.json    a mix's parameters; its ``kind``
                                    names the module below
    bench/traffic/<kind>.py         ``plan``, ``drive`` (see
                                    ``bench.lib.traffic``)
    bench/metrics/<metric>.py       ``read(run) -> float | None``
    bench/costs/<name>.py           ``flops(cfg, ...)``, ``bytes(cfg, ...)``
    bench/reference/<name>.py       plain float32 reference of a family
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, List, Optional

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


class SpecError(ValueError):
    """A cell, file or metric that cannot be found or read."""


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    reader: Any                      # module with read(run)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    kind: Any                        # bench/traffic/<kind>.py
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _load_json(path: str) -> Dict[str, Any]:
    if not os.path.isfile(path):
        raise SpecError(f"missing file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, bench: str = BENCH):
    """Import ``bench/<kind>/<name>.py`` by path (names may hold dots)."""
    path = os.path.join(bench, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise SpecError(f"no {kind} file for {name!r}: "
                        f"{os.path.relpath(path, os.path.dirname(bench))}")
    mod_name = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_config(name: str, bench: str = BENCH) -> Dict[str, Any]:
    cfg = _load_json(os.path.join(bench, "configs", f"{name}.json"))
    if cfg.get("name") != name:
        raise SpecError(f"configs/{name}.json names itself "
                        f"{cfg.get('name')!r}")
    return cfg


def load_traffic(name: str, bench: str = BENCH) -> Dict[str, Any]:
    return _load_json(os.path.join(bench, "traffic", f"{name}.json"))


def _applies(entry: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(workload: str, *, benchmark: Optional[Dict[str, Any]] = None,
              bench: str = BENCH) -> Cell:
    """The cell ``workload`` of ``BENCHMARK.json`` with its files."""
    if benchmark is None:
        benchmark = _load_json(os.path.join(os.path.dirname(bench),
                                            "BENCHMARK.json"))
    entry = next((w for w in benchmark["workloads"]
                  if w["name"] == workload), None)
    if entry is None:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json; "
                        f"have {[w['name'] for w in benchmark['workloads']]}")

    def metrics(kind: str) -> List[Metric]:
        return [Metric(name=m["name"], unit=m["unit"],
                       reader=load_module("metrics", m["name"], bench))
                for m in benchmark[kind] if _applies(m, workload)]

    mix = load_traffic(entry["traffic"], bench)
    if "kind" not in mix:
        raise SpecError(f"traffic/{entry['traffic']}.json names no kind")
    return Cell(name=workload, chips=int(entry["chips"]),
                config=load_config(entry["config"], bench),
                traffic=mix, kind=load_module("traffic", mix["kind"], bench),
                end_to_end=metrics("end_to_end"),
                per_layer=metrics("per_layer"))
