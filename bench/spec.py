"""Where the benchmark finds each of its parts, by the names in BENCHMARK.json.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by name, so a later change adds a cell by adding
files and entries and edits nothing that is there:

    BENCHMARK.json                      cells, metrics, bounds
    bench/configs/<config>.json         sizes, dtype, init, deployment
    bench/traffic/<traffic>.json        arrival process and length mix
    bench/limits/<workload>.json        limits of the correctness check
    bench/e2e/<metric>.py               end-to-end metric: read(run)
    bench/metrics/<metric>.py           per-layer metric:  read(ctx)
    bench/adapters/<family>.py          weights -> the program's pytree
    bench/reference/<family>.py         plain float32 reference
    bench/flops_<family>.py             operations and bytes (dense: flops.py)
"""
from __future__ import annotations

import importlib.util
import json
import os
from types import ModuleType

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(RuntimeError):
    pass


def _read_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise SpecError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str) -> ModuleType:
    """Import a Python file by path (metric files carry dots in their names)."""
    if not os.path.isfile(path):
        raise SpecError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(f"bench_dyn.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """BENCHMARK.json and the files it names, under one root."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.dir = os.path.join(root, "bench")
        self.doc = _read_json(os.path.join(root, "BENCHMARK.json"))

    def path(self, *parts: str) -> str:
        return os.path.join(self.dir, *parts)

    def workload(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                cfg = _read_json(os.path.join(self.root, c["file"]))
                cfg.setdefault("name", name)
                return cfg
        raise SpecError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return _read_json(self.path("traffic", f"{name}.json"))

    def limits(self, workload: str) -> dict:
        return _read_json(self.path("limits", f"{workload}.json"))

    def _applies(self, metric: dict, workload: str) -> bool:
        cells = metric.get("workloads")
        return cells is None or workload in cells

    def end_to_end(self, workload: str) -> list[dict]:
        return [m for m in self.doc["end_to_end"] if self._applies(m, workload)]

    def per_layer(self, workload: str) -> list[dict]:
        e2e = {m["name"] for m in self.end_to_end(workload)}
        return [m for m in self.doc["per_layer"]
                if self._applies(m, workload) and m["moves"] in e2e]

    def e2e_reader(self, metric: str) -> ModuleType:
        return load_module(self.path("e2e", f"{metric}.py"), f"e2e.{metric}")

    def metric_reader(self, metric: str) -> ModuleType:
        return load_module(self.path("metrics", f"{metric}.py"), f"metrics.{metric}")

    def adapter(self, family: str) -> ModuleType:
        return load_module(self.path("adapters", f"{family}.py"), f"adapters.{family}")

    def reference(self, family: str) -> ModuleType:
        return load_module(self.path("reference", f"{family}.py"), f"reference.{family}")

    def peaks(self) -> dict:
        return _read_json(self.path("peaks.json"))
