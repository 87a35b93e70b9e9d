"""BENCHMARK.json -> one cell: its configuration, its traffic mix and the
metrics that apply to it. Nothing here knows a cell, a metric or a
configuration by name."""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class BenchError(Exception):
    """The run cannot produce a result line (exit code 2)."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration's file, as it is run
    traffic: dict         # the traffic mix's parameters
    end_to_end: list      # metric entries of BENCHMARK.json for this cell
    per_layer: list


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, bench_path: str | None = None) -> Cell:
    bench = _load_json(bench_path or os.path.join(ROOT, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise BenchError(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{sorted(by_name)}")
    w = by_name[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = _load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = _load_json(os.path.join(BENCH_DIR, "traffic",
                                      w["traffic"] + ".json"))
    return Cell(
        name=workload, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])


def load_plugin(kind: str, name: str):
    """perfbench/<kind>/<name>.py as a module. Metric names carry dots, so
    the file is loaded by path, not by import name."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.exists(path):
        raise BenchError(f"no {kind} file for {name!r}: expected {path}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
