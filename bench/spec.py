"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

  configuration  the file named by its ``configs`` entry
  traffic mix    bench/traffic/<traffic>.json
  cell           bench/cells/<workload>.json (offered rate, fill)
  metric         bench/metrics/<metric>.py, a module with ``read(ctx)``

A new configuration, mix, cell or metric is new files plus new entries in
``BENCHMARK.json``; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict           # the configuration file as run
    traffic_name: str
    traffic: dict          # the mix file
    params: dict           # the cell file
    end_to_end: list       # BENCHMARK.json entries of the cell's metrics
    per_layer: list


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: pathlib.Path, name: str) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files read."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; one of {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    conf = configs[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=load_json(root / conf["file"]),
        traffic_name=w["traffic"],
        traffic=load_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        params=load_json(root / "bench" / "cells" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def reader(root: pathlib.Path, metric: str):
    """The ``read(ctx)`` function of bench/metrics/<metric>.py."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
