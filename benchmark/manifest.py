"""BENCHMARK.json and the files it names. Everything that belongs to one
configuration, one traffic mix or one per-layer metric is a file found
by its name:

- configuration ``c``  -> the ``file`` of its ``configs`` entry
  (``benchmark/configs/<c>.json``);
- traffic mix ``t``    -> ``benchmark/traffic/<t>.json``;
- per-layer metric ``m`` -> ``benchmark/layers/<m up to its first '.'>.py``
  with ``read(run, name)``;
- driver ``d`` (named by the configuration file) ->
  ``benchmark/drivers/<d>.py`` with ``run(ctx)``.
"""

from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(path: str) -> dict:
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json (have "
                     f"{[w['name'] for w in manifest['workloads']]})")


def config_of(manifest: dict, cell_: dict) -> dict:
    for c in manifest["configs"]:
        if c["name"] == cell_["config"]:
            return _json(c["file"])
    raise SystemExit(f"no config {cell_['config']!r} in BENCHMARK.json")


def traffic_path(name: str) -> str:
    return os.path.join("benchmark", "traffic", name + ".json")


def traffic_of(cell_: dict) -> dict:
    return _json(traffic_path(cell_["traffic"]))


def metrics_for(manifest: dict, group: str, cell_name: str) -> list:
    """The entries of ``end_to_end`` or ``per_layer`` this cell reports:
    those with no ``workloads`` key, or that list the cell."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def reader_path(metric: str) -> str:
    return os.path.join("benchmark", "layers", metric.split(".")[0] + ".py")


def reader_of(metric: str):
    return importlib.import_module(
        "benchmark.layers." + metric.split(".")[0])


def driver_of(config: dict):
    return importlib.import_module("benchmark.drivers." + config["driver"])
