"""Tiny versions of the benchmark's configurations and mixes, small
enough for a CPU test: short records and files, pings close enough that
every record holds events.  Built from the files under ``bench/`` by
name, whether or not ``BENCHMARK.json`` has a cell for them."""
from __future__ import annotations

import copy
import json

from bench.harness.spec import BENCH, Cell

SMALL = {"record_size_sec": 0.25, "file_sec": 1.5}
# (configuration, mix, limits, chips) of each layout the tests run
LAYOUTS = {
    "set1.full": ("depam_set1", "full", "set1.full", 1),
    "set2.full": ("depam_set2", "full", "set2.full", 1),
    "set1.ltsa": ("depam_set1", "ltsa", "set1.full", 1),
    "set1x4.full": ("depam_set1_x4", "full", "set1.full", 4),
}


def _load(path: str) -> dict:
    return json.loads((BENCH / path).read_text())


def tiny_cell(name: str) -> Cell:
    config, traffic, limits, chips = LAYOUTS[name]
    cell = copy.copy(Cell("set1.full"))
    cell.name, cell.chips, cell.traffic = name, chips, traffic
    cell.config = dict(_load(f"configs/{config}.json"), **SMALL)
    cell.mix = _load(f"mixes/{traffic}.json")
    cell.mix["signal"].update(ping_gap_sec=[0.08, 0.2])
    wanted = {"ltsa": {"welch_rel", "spl_db", "tol_db", "ltsa_rel",
                       "mean_welch_rel"}}.get(traffic)
    cell.limits = {k: v for k, v in _load(f"limits/{limits}.json").items()
                   if wanted is None or k in wanted}
    return cell
