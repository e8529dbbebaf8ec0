"""Finds everything the benchmark runs by its name in ``BENCHMARK.json``.

Nothing here lists cells, configurations, traffic mixes, drivers or
metrics: each is a file under the benchmark's directory, found by name.

    bench/cells/<workload>.json      engine sizing, rate, check limits
    bench/configs/<config>.json      the model's sizes, as run
    bench/traffic/<traffic>.json     lengths and arrivals
    bench/drivers/<driver>.py        how to drive one entry point
    bench/metrics/<metric>.py        one per-layer metric's reader
    bench/flops/<attention>.py       FLOPs of one attention kind
    bench/reference/<attention>.py   the plain float32 reference
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from types import ModuleType
from typing import Any

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(RuntimeError):
    """A name in BENCHMARK.json has no file, or a file is malformed."""


def _read_json(path: str) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


_modules: dict[str, ModuleType] = {}


def load_module(path: str) -> ModuleType:
    """Import a file by path; the file name may hold dots (``mfu.decode.py``)."""
    path = os.path.abspath(path)
    if path in _modules:
        return _modules[path]
    if not os.path.isfile(path):
        raise SpecError(f"missing file {path}")
    name = "bench_dyn_" + os.path.relpath(path, BENCH_DIR).replace(os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    _modules[path] = mod
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of BENCHMARK.json with everything it names, loaded."""

    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict  # bench/configs/<config>.json
    traffic: dict  # bench/traffic/<traffic>.json
    cell: dict  # bench/cells/<workload>.json
    end_to_end: list[dict]  # metrics of BENCHMARK.json this cell reports
    per_layer: list[dict]
    bench_dir: str

    def path(self, *parts: str) -> str:
        return os.path.join(self.bench_dir, *parts)

    def driver(self) -> ModuleType:
        return load_module(self.path("drivers", self.cell["driver"] + ".py"))

    def flops(self) -> ModuleType:
        return load_module(self.path("flops", self.config["attention"] + ".py"))

    def reference(self) -> ModuleType:
        return load_module(self.path("reference", self.config["attention"] + ".py"))

    def metric_reader(self, name: str) -> ModuleType:
        return load_module(self.path("metrics", name + ".py"))


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_benchmark(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def load_cell(workload: str, root: str = ROOT, bench_dir: str | None = None) -> Cell:
    bench = load_benchmark(root)
    bench_dir = bench_dir or os.path.join(root, bench["paths"][0])
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json")
    w = by_name[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {workload!r} names unknown config {w['config']!r}")
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config_name=w["config"],
        traffic_name=w["traffic"],
        config=_read_json(os.path.join(root, configs[w["config"]]["file"])),
        traffic=_read_json(os.path.join(bench_dir, "traffic", w["traffic"] + ".json")),
        cell=_read_json(os.path.join(bench_dir, "cells", workload + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, workload)],
        bench_dir=bench_dir,
    )
