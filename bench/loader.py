"""Finds a cell's parts by name: the benchmark is data.

``BENCHMARK.json`` lists the cells, configurations and metrics.  Each
configuration is the JSON file that its entry names; a cell's traffic
mix is ``traffic/<traffic>.json``; the graph generator and the
algorithm a configuration names are ``graphs/<graph>.py`` and
``algorithms/<algorithm>.py``; every metric is read by
``metrics/<metric>.py``, whose ``read(run)`` returns a number or None.
Adding a cell, a configuration or a metric adds files and entries and
edits none.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.isfile(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Benchmark:
    """``BENCHMARK.json`` and the files it names, under ``root``."""

    def __init__(self, root: str = ROOT, bench_dir: str = BENCH_DIR):
        self.root = root
        self.bench_dir = bench_dir
        self.spec = _json(os.path.join(root, "BENCHMARK.json"))

    def _entry(self, key: str, name: str) -> dict:
        for e in self.spec[key]:
            if e["name"] == name:
                return e
        raise KeyError(f"no {key} entry named {name!r}")

    def cell(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        return _json(os.path.join(self.root, self._entry("configs",
                                                          name)["file"]))

    def traffic(self, name: str) -> dict:
        return _json(os.path.join(self.bench_dir, "traffic", name + ".json"))

    def graph_generator(self, name: str):
        return load_module(os.path.join(self.bench_dir, "graphs",
                                        name + ".py"), "graph_" + name)

    def algorithm(self, name: str):
        return load_module(os.path.join(self.bench_dir, "algorithms",
                                        name + ".py"), "algorithm_" + name)

    def metrics(self, cell: str, trace: bool) -> list:
        """The metrics a run of ``cell`` reports: its end-to-end metrics,
        or with ``trace`` its per-layer ones."""
        key = "per_layer" if trace else "end_to_end"
        return [m for m in self.spec[key]
                if cell in m.get("workloads", [cell])]

    def reader(self, metric: str):
        return load_module(os.path.join(self.bench_dir, "metrics",
                                        metric + ".py"),
                           "metric_" + metric.replace(".", "_"))
