#!/usr/bin/env python3
"""The readings that a cell's limits are set from, in one process.

    python3 bench/calibrate.py --workload <name> --seeds <first> <n> \
        [--control <n>] [--out FILE]

For each of ``n`` seeds from ``first``: the corpus, one job of the
timed path (the window's own builder, programs and sizes), and every
compared number against the float64 reference; then, on the first
``--control`` seeds, the same numbers for the control, the reference
computed one precision below the configuration's.  Each seed's
readings are printed as one JSON line.  A limit lies above the largest
reading of the program and below the smallest of the control (see
``PERF.md``).  Runs on the chip only, like ``run.py``.
"""
import argparse
import json
import os
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs=2, required=True,
                    metavar=("FIRST", "N"))
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--out")
    a = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    # the compile cache lives at a fixed path inside the checkout, so
    # only a cell's first run there compiles; the program takes it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")

    import jax
    from bench.harness import check, control, corpus
    from bench.harness.spec import BENCH, Cell
    from bench.harness.window import Runner
    from repro.kernels import common
    from repro.launch import runtime

    cell = Cell(a.workload)
    if jax.devices()[0].platform != "tpu" or common.use_interpret() \
            or len(jax.devices()) < cell.chips:
        sys.exit("calibrate: runs on the chip only")
    os.makedirs(runtime.enable_compile_cache(), exist_ok=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    work = BENCH / ".work" / f"calibrate-{cell.name}"
    rows = []
    first, n = a.seeds
    for k, seed in enumerate(range(first, first + n)):
        shutil.rmtree(work, ignore_errors=True)
        data = str(work / "corpus")
        t = time.perf_counter()
        corpus.write_corpus(data, cell.config, cell.mix, seed)
        runner = Runner(cell.config, cell.mix, data, str(work))
        job = runner.run_one("job")
        ref = check.Reference(data, cell.config, cell.mix, seed)
        got, info = check.readings(
            check.Outputs.of_job(job.store, job.result), ref)
        row = {"seed": seed, "side": "program", **got, **info}
        rows.append(row)
        print(json.dumps(row), flush=True)
        if k < a.control:
            got, info = check.readings(control.control_outputs(data, ref),
                                       ref)
            row = {"seed": seed, "side": "control", **got, **info}
            rows.append(row)
            print(json.dumps(row), flush=True)
        print(f"seed {seed}: {time.perf_counter() - t:.1f} s",
              file=sys.stderr, flush=True)
    shutil.rmtree(work, ignore_errors=True)
    if a.out:
        pathlib.Path(a.out).parent.mkdir(parents=True, exist_ok=True)
        with open(a.out, "a") as f:
            for row in rows:
                f.write(json.dumps({"workload": cell.name, **row}) + "\n")


if __name__ == "__main__":
    main()
