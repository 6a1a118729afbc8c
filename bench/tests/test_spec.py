"""The harness finds everything a cell is made of by name, so a new
cell is data: a mix file, a limits file and entries in BENCHMARK.json."""
import json
import shutil

import jax
import pytest

from bench.harness import measure
from bench.harness.spec import ROOT, Cell, benchmark
from bench.tests.tiny import tiny_cell


def test_every_cell_resolves():
    bench = benchmark()
    for w in bench["workloads"]:
        cell = Cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.mix["features"]
        assert cell.limits
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert callable(cell.reader(m["name"]))
    for m in bench["per_layer"]:
        for w in m["workloads"]:
            assert w in {x["name"] for x in bench["workloads"]}


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError, match="no workload named"):
        Cell("no.such.cell")


def test_an_added_mix_runs_without_editing_a_file(tmp_path):
    """A copy of the benchmark gains a mix file, a limits file and a
    workload entry; the harness runs the new cell unchanged."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    bench = benchmark()
    mix = json.loads((ROOT / "bench/mixes/ltsa.json").read_text())
    mix["features"] = ["welch", "spl", "tol", "ltsa", "minmax"]
    (tmp_path / "bench/mixes/envelope.json").write_text(json.dumps(mix))
    limits = json.loads((ROOT / "bench/limits/set1.full.json").read_text())
    (tmp_path / "bench/limits/set1.envelope.json").write_text(
        json.dumps({k: limits[k] for k in ("welch_rel", "spl_db", "tol_db",
                                           "ltsa_rel", "mean_welch_rel",
                                           "minmax_rel")}))
    bench["workloads"].append({"name": "set1.envelope",
                               "config": "depam_set1",
                               "traffic": "envelope", "chips": 1,
                               "why": "test"})
    for m in bench["per_layer"] + bench["end_to_end"]:
        if "workloads" in m and "set1.full" in m["workloads"]:
            m["workloads"].append("set1.envelope")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = Cell("set1.envelope", root=tmp_path)
    assert cell.mix["features"][-1] == "minmax"
    small = tiny_cell("set1.full")
    cell.config, cell.mix["signal"] = small.config, small.mix["signal"]
    result, lines = measure.run(cell, 2**31 + 11, 1.0, False,
                                jax.devices()[:1], measure.now())
    assert result["correct"], lines
    assert "minmax_rel" in result["compared"]
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
