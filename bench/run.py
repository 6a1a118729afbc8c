#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up writes the cell's corpus from the seed, loads the program from
the compile cache and runs one whole warm-up job; the window then runs
complete jobs of the real batch path back to back for ``--seconds``,
each into a fresh store.  After the window the committed outputs of a
job are compared with the float64 reference, and the last line of
standard output is the result as one JSON object: the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``
(the first job of the window then runs under the profiler).  The
numbers compared, each with its limit, are the last lines of standard
error and the last key of the result.

It exits non-zero, with no result, when JAX finds no TPU or fewer chips
than the cell asks for, or when the kernels would run in interpret mode.
"""
import time

START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def fail(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr)
    sys.exit(2)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    # the compile cache lives at a fixed path inside the checkout, so
    # only a cell's first run there compiles; the program takes it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    # libtpu writes its logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench.harness.spec import Cell
    try:
        cell = Cell(a.workload)
    except (KeyError, FileNotFoundError) as e:
        fail(f"cannot read cell {a.workload!r}: {e}")

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"JAX found no TPU (platform {devices[0].platform!r}); the "
             f"benchmark runs on the chip only")
    if len(devices) < cell.chips:
        fail(f"cell {cell.name} needs {cell.chips} chips, JAX found "
             f"{len(devices)}")
    from repro.kernels import common
    if common.use_interpret():
        fail("the Pallas kernels would run in interpret mode")

    from bench.harness import measure
    result, checked = measure.run(cell, a.seed, a.seconds, bool(a.trace),
                                  devices[:cell.chips], START)
    for line in checked:
        print(line, file=sys.stderr)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
