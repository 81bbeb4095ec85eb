"""Find a cell's parts by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix, and each
metric.  Their files live at fixed places under ``bench/``:

* ``bench/configs/<config>/config.json`` — the configuration as it is run,
  and ``bench/configs/<config>/model.py`` — its plain reference, weight
  maker and operation/byte counts;
* ``bench/traffic/<traffic>.json`` — the mix's parameters, read by the one
  generator in :mod:`bench.harness.traffic`;
* ``bench/metrics/<metric>.py`` — one reader per metric.

Adding a cell, a mix or a metric therefore adds files and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from types import ModuleType

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str) -> ModuleType:
    """Import the file at ``path`` as a module of its own (names may hold
    ``-`` and ``.``, so the import system's dotted names cannot be used)."""
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ident(name: str) -> str:
    return "".join(c if c.isalnum() else "_" for c in name)


def config_dir(name: str) -> str:
    return os.path.join(BENCH_DIR, "configs", name)


def load_config(name: str) -> tuple[dict, ModuleType]:
    """The configuration's file and its reference module."""
    d = config_dir(name)
    cfg = load_json(os.path.join(d, "config.json"))
    model = load_module(os.path.join(d, "model.py"),
                        "bench_config_" + _ident(name))
    return cfg, model


def load_traffic(name: str) -> dict:
    return load_json(os.path.join(BENCH_DIR, "traffic", f"{name}.json"))


def load_metric(name: str) -> ModuleType:
    return load_module(os.path.join(BENCH_DIR, "metrics", f"{name}.py"),
                       "bench_metric_" + _ident(name))


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with the metrics it reports."""

    name: str
    config_name: str
    traffic_name: str
    chips: int
    end_to_end: list[dict]
    per_layer: list[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(bench: dict, name: str) -> Cell:
    for w in bench["workloads"]:
        if w["name"] == name:
            return Cell(
                name=name, config_name=w["config"],
                traffic_name=w["traffic"], chips=int(w["chips"]),
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def load_benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))
