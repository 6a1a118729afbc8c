"""One run of a cell: set-up, the window, the metrics, the check."""
from __future__ import annotations

import glob
import os
import shutil

import numpy as np

from . import check, corpus
from .spec import BENCH, Cell
from .timing import now
from .window import Runner


def _device(devices, trace_run=None) -> dict:
    d = devices[0]
    out = {"platform": d.platform, "kind": d.device_kind,
           "count": len(devices),
           "memory_peak_bytes": max(
               int((x.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for x in devices)}
    if trace_run is not None:
        out["busy_s"] = trace_run.busy_s()
        out["window_s"] = trace_run.window_s
    return out


def end_to_end(cell: Cell, runner: Runner, t0: float, t1: float,
               setup_s: float) -> tuple[dict, int]:
    """Audio committed in the window over its seconds, the 95th
    percentile of every committed record's lag, and the set-up time."""
    n, lags = runner.committed_in(t0, t1)
    values = {
        "audio_x_realtime": n * cell.config["record_size_sec"] / (t1 - t0),
        "result_lag_p95_ms": (float(np.percentile(lags, 95)) * 1e3
                              if lags.size else float("inf")),
        "setup_s": setup_s,
    }
    return values, n


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices,
        start: float) -> tuple[dict, list[str]]:
    import jax
    from repro.launch import runtime

    os.makedirs(runtime.enable_compile_cache(), exist_ok=True)
    # every program of the cell, however quick to compile, is kept, so
    # only the first run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    work = BENCH / ".work" / cell.name
    shutil.rmtree(work, ignore_errors=True)
    data = str(work / "corpus")
    compiles = []

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append((now(), duration))

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        corpus.write_corpus(data, cell.config, cell.mix, seed)
        t_corpus = now()
        runner = Runner(cell.config, cell.mix, data, str(work))
        runner.warm_up()
        trace_dir = str(work / "trace") if trace else None
        t0, t1 = runner.window(seconds, trace_dir)
        t_end = now()
        values, n = end_to_end(cell, runner, t0, t1, t0 - start)
        traced = None
        if trace:
            from .trace import TracedRun
            pb = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
            traced = TracedRun(pb[0], cell, runner.traced_modules,
                               len(runner.jobs[0].record.commits),
                               devices[0].device_kind, len(devices))
        device = _device(devices, traced)

        done = runner.completed()
        if not done:
            raise RuntimeError("no job completed inside the window; the "
                               "window is shorter than one job")
        pick = done[int(np.random.default_rng(
            corpus.seed_words(seed) + [11]).integers(len(done)))]
        ref = check.Reference(data, cell.config, cell.mix, seed)
        got, info = check.readings(
            check.Outputs.of_job(pick.store, pick.result), ref)
        ok, lines = check.verdict(got, cell.limits)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
        shutil.rmtree(work, ignore_errors=True)

    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = cell.reader(m["name"])(traced)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    facts = " ".join(f"{k} {v}" for k, v in info.items())
    bounds = [f"roofline: {k} bound by {v}"
              for k, v in sorted(traced.bounds.items())] if traced else []
    jobs = runner.jobs
    lines = [f"setup: corpus {t_corpus - start:.3f} s, warm-up "
             f"{t0 - t_corpus:.3f} s; window: {len(jobs)} jobs "
             f"({len(runner.completed())} whole), {n} records committed, "
             f"last job ended {t_end - t1:.3f} s after the close; "
             f"compiles: {sum(c < t0 for c, _ in compiles)} in set-up "
             f"({sum(d for c, d in compiles if c < t0):.3f} s), "
             f"{sum(t0 <= c <= t1 for c, _ in compiles)} inside the window",
             *bounds, f"check: {facts}", *lines]
    result = {"correct": bool(ok), "attempted": int(n), "failed": 0,
              "metrics": metrics, "device": device}
    if traced is not None:
        result["breakdown"] = traced.breakdown()
    result["compared"] = {name: {"value": got.get(name),
                                 "limit": cell.limits.get(name)}
                          for name in sorted(set(got) | set(cell.limits))}
    return result, lines
