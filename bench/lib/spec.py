"""The benchmark's data, found by name.

Everything that belongs to one configuration, traffic mix, cell or metric
sits in a file of its own, and the harness finds it by the name that
``BENCHMARK.json`` gives:

  bench/configs/<config>.json    model sizes as run, and the zero-load fit
  bench/traffic/<traffic>.json   the mix: classes, lengths, clip, drain
  bench/cells/<workload>.json    the cell's fixed arrival rate (its knee)
  bench/metrics/<metric>.py      one reader per metric, ``read(run)``

A later change adds a configuration, a mix, a cell or a metric by adding
files and entries, never by editing these.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_file(spec: dict, name: str) -> dict:
    for c in spec["configs"]:
        if c["name"] == name:
            return load_json(ROOT / c["file"])
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic_file(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def cell_file(name: str) -> dict:
    return load_json(BENCH / "cells" / f"{name}.json")


def metrics_of(spec: dict, cell: str, section: str) -> list[dict]:
    """The metrics of ``section`` ("end_to_end" or "per_layer") that this
    cell reports: those without a ``workloads`` list, and those whose
    list names the cell."""
    return [m for m in spec[section]
            if "workloads" not in m or cell in m["workloads"]]


def reader(name: str):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    mod_name = "bench_metric_" + name.replace(".", "_").replace("-", "_")
    s = importlib.util.spec_from_file_location(mod_name, path)
    if s is None or s.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod.read
