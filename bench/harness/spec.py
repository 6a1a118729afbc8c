"""Finds what a cell is made of by name: its entry in ``BENCHMARK.json``,
its configuration file, its traffic mix, its limits and the readers of
its per-layer metrics.

A cell names a configuration and a traffic mix; everything else follows
from the names, so a new cell, mix or metric is a new file plus an entry
in ``BENCHMARK.json``:

  * ``BENCHMARK.json`` ``configs[].file``: the deployment (sizes, layout);
  * ``bench/mixes/<traffic>.json``: what the job computes and the signal;
  * ``bench/limits/<workload>.json``: the limit of each compared number;
  * ``bench/metrics/<metric>.py``: one reader per per-layer metric.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def benchmark(root: pathlib.Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _by_name(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json "
                   f"(have {sorted(e['name'] for e in entries)})")


def _load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    def __init__(self, name: str, root: pathlib.Path = ROOT):
        bench = benchmark(root)
        self.root = root
        self.name = name
        self.entry = _by_name(bench["workloads"], name, "workload")
        self.chips = int(self.entry["chips"])
        cfg_entry = _by_name(bench["configs"], self.entry["config"],
                             "configuration")
        self.config = _load_json(root / cfg_entry["file"])
        self.traffic = self.entry["traffic"]
        self.mix = _load_json(root / "bench" / "mixes"
                              / f"{self.traffic}.json")
        self.limits = _load_json(root / "bench" / "limits" / f"{name}.json")
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])
                          and m["moves"] in reported]

    def reader(self, metric: str):
        """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
        path = self.root / "bench" / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + metric.replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
