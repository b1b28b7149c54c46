"""Resolve a benchmark cell from ``BENCHMARK.json`` by name.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

* configuration ``<config>``   -> the ``file`` its entry names
  (``bench/configs/<config>.json``)
* traffic mix ``<traffic>``    -> ``bench/traffic/<traffic>.json``
* per-layer metric ``<metric>`` -> ``bench/metrics/<metric>.py``, a module
  with ``read(ctx) -> float | None``

A later cell, mix or metric is added as new files plus new entries; no
file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file, as run
    traffic: dict           # the traffic file's parameters
    end_to_end: list        # BENCHMARK.json metric entries this cell reports
    per_layer: list
    readers: dict           # per-layer metric name -> read(ctx)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def load_reader(name: str, bench_dir: str = BENCH_DIR):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"),
        os.path.join(bench_dir, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(bench: dict, workload: str, bench_dir: str = BENCH_DIR) -> Cell:
    """The cell named ``workload``, with its files loaded. Raises
    ``KeyError`` for an unknown cell and ``OSError`` for a missing file."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = _load_json(os.path.join(os.path.dirname(bench_dir),
                                  configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(bench_dir, "traffic",
                                      f"{w['traffic']}.json"))
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload)]
    per_layer = [m for m in bench["per_layer"] if _reports(m, workload)]
    readers = {m["name"]: load_reader(m["name"], bench_dir)
               for m in per_layer}
    return Cell(name=workload, chips=int(w["chips"]), config=cfg,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer,
                readers=readers)
