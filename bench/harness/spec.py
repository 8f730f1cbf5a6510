"""What a run measures, found by name.

``BENCHMARK.json`` names the cells (workloads), configurations, traffic
mixes and metrics.  Everything that belongs to one of them lives in a
file of its own under ``bench/``, found from its name:

    bench/configs/<config>.json          sizes, protocol, objective
    bench/traffic/<traffic>.json         the call mix; names its driver
    bench/limits/<workload>.json         the limits ``correct`` uses
    bench/drivers/<driver>.py            the loop that offers the load
    bench/generators/<generator>.py      data from the seed, on device
    bench/references/<reference>.py      plain f32 references
    bench/kernel_checks/<kernel>.py      one Pallas kernel vs its reference
    bench/work/<kernel>.py               a kernel's required FLOPs/bytes
    bench/metrics/<metric>.py            one per-layer metric

so a new cell, mix, configuration or metric is a new file and a new
entry, never an edit.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass, field

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, bench_dir: str = BENCH):
    """Import ``bench/<kind>/<name>.py`` as a fresh module."""
    path = os.path.join(bench_dir, kind, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} named {name!r} ({path})")
    modname = f"bench_{kind}_{name.replace('-', '_').replace('.', '_')}"
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)
    bench_dir: str = BENCH

    @property
    def sizes(self) -> dict:
        """The configuration's sizes with the traffic's overrides
        (``k``) applied: the logical shapes of this cell."""
        s = dict(self.config["sizes"])
        s.update(self.traffic.get("sizes", {}))
        return s

    @property
    def algo(self) -> str:
        return self.traffic["algo"]

    @property
    def options(self) -> dict:
        return dict(self.traffic.get("options", {}))

    def module(self, kind: str, name: str):
        return load_module(kind, name, self.bench_dir)


def _applies(metric: dict, workload: str, reported: set) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in reported


def load_cell(workload: str, root: str = ROOT) -> Cell:
    """Load ``workload`` from ``<root>/BENCHMARK.json`` and the files
    under ``<root>/bench``."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    bench_dir = os.path.join(root, "bench")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    reported = {m["name"] for m in e2e}
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=load_json(os.path.join(root, configs[w["config"]]["file"])),
        traffic=load_json(os.path.join(bench_dir, "traffic",
                                       f"{w['traffic']}.json")),
        limits=load_json(os.path.join(bench_dir, "limits",
                                      f"{workload}.json")),
        end_to_end=e2e,
        per_layer=[m for m in bench["per_layer"]
                   if _applies(m, workload, reported)],
        bench_dir=bench_dir,
    )
