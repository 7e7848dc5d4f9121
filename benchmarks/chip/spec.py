"""What a cell is, read from ``BENCHMARK.json`` and the files it names.

A cell names a configuration (``configs/<name>.json``, by the file that
``BENCHMARK.json`` gives it), a traffic mix (``traffic/<mix>.json``) and a
chip count; its per-layer metrics are readers in ``metrics/<metric>.py``
and its correctness limits are in ``limits/<cell>.json``.  A new cell,
mix, configuration or metric is new files and entries: nothing here
changes.
"""
from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _json(path):
    with open(path) as f:
        return json.load(f)


def benchmark(root=ROOT):
    return _json(os.path.join(root, "BENCHMARK.json"))


class Cell:
    """One entry of ``workloads`` with everything it names."""

    def __init__(self, name, chips, config, traffic, end_to_end, per_layer):
        self.name = name
        self.chips = chips
        self.config = config
        self.model = config["model"]
        self.traffic = traffic
        self.end_to_end = end_to_end
        self.per_layer = per_layer

    @classmethod
    def load(cls, name, root=ROOT):
        bench = benchmark(root)
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
        w = cells[name]
        configs = {c["name"]: c for c in bench["configs"]}
        return cls(
            name, w["chips"],
            _json(os.path.join(root, configs[w["config"]]["file"])),
            _json(os.path.join(HERE, "traffic", f"{w['traffic']}.json")),
            [m for m in bench["end_to_end"]
             if name in m.get("workloads", [name])],
            [m for m in bench["per_layer"]
             if name in m.get("workloads", [name])])


def reader(metric_name):
    """The module that reads one per-layer metric (``metrics/<name>.py``):
    ``read(observed)`` returns the value, or None where the run holds
    nothing for it to read."""
    path = os.path.join(HERE, "metrics", f"{metric_name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric_name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
