#!/usr/bin/env python3
"""Record the chip trace that ``test_trace.py`` reads: four steps of a
job of a cell, with the benchmark's spans on, one TPU.

    python3 bench/tests/record_trace.py set1.full

Writes ``bench/tests/data/<cell>.xplane.pb`` and ``<cell>.json`` (the
programs' module names, the steps, the device kind).
"""
import glob
import json
import os
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main(name: str) -> None:
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax
    from bench.harness import corpus, window
    from bench.harness.spec import BENCH, Cell
    from bench.harness.timing import JobRecord

    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace: runs on the chip only")
    cell = Cell(name)
    work = BENCH / ".work" / f"record-{name}"
    shutil.rmtree(work, ignore_errors=True)
    data = str(work / "corpus")
    corpus.write_corpus(data, cell.config, cell.mix, 5)
    runner = window.Runner(cell.config, cell.mix, data, str(work))
    runner.warm_up()
    record = JobRecord(runner.m.n_records, trace=True)
    with window.programs() as names:
        jax.profiler.start_trace(str(work / "trace"))
        try:
            runner.job(str(work / "store"), record).limit(4).run()
        finally:
            jax.profiler.stop_trace()
    out = BENCH / "tests" / "data"
    out.mkdir(exist_ok=True)
    pb = glob.glob(f"{work}/trace/**/*.xplane.pb", recursive=True)[0]
    shutil.copy(pb, out / f"{name}.xplane.pb")
    (out / f"{name}.json").write_text(json.dumps({
        "workload": name, "modules": names, "steps": len(record.commits),
        "device_kind": jax.devices()[0].device_kind}) + "\n")
    shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
