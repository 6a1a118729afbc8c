"""Set-up and the measured window of a cell: the real batch path,
``api.job(...)...run()``, over the generated corpus, job after job.

Every job is built by the same builder calls the batch launcher makes
(``launch/depam_run.py``): ``features``, ``chunk``, per-file
``window``, a ``WavSource`` on the corpus, the ``int16`` payload,
``events(..., impulsive=True)``, a fresh resumable ``FeatureStore`` and
``async_io()`` with the program's own depths.  The four-chip layout adds
``.shards(n).on(make_host_mesh(data=n))``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import shutil

import numpy as np

from .timing import JobRecord, TimedSink, TimedSource, WindowClosed, now


def params(config: dict):
    from repro.core.params import DepamParams
    return DepamParams(
        fs=float(config["fs"]), nfft=config["nfft"],
        window_size=config["window_size"],
        window_overlap=config["window_overlap"],
        record_size_sec=float(config["record_size_sec"]),
        window=config["window"])


@contextlib.contextmanager
def programs():
    """The module names of the feature-step and reduce-update programs
    the jobs inside the block build, as the trace names them: ``jit_``
    and the jitted function's name."""
    from repro.api import engine

    names: dict[str, str] = {}
    real = {"step": engine.compile_step,
            "reduce": engine.compile_reduce_update}

    def builder(which):
        def build(*args, **kw):
            fn = real[which](*args, **kw)
            names[which] = f"jit_{fn.__name__}"
            return fn
        return build

    engine.compile_step = builder("step")
    engine.compile_reduce_update = builder("reduce")
    try:
        yield names
    finally:
        engine.compile_step = real["step"]
        engine.compile_reduce_update = real["reduce"]


@dataclasses.dataclass
class JobRun:
    record: JobRecord
    store: str
    result: object = None          # JobResult of a job that completed


class Runner:
    """Builds and runs the cell's jobs over one corpus."""

    def __init__(self, config: dict, mix: dict, data_dir: str,
                 work_dir: str):
        from repro import api
        self.config, self.mix = config, mix
        self.data_dir, self.work_dir = data_dir, work_dir
        self.p = params(config)
        self.m = api.scan_dataset(data_dir, self.p.record_size, seed=42)
        self.mesh = None
        if config.get("mesh_data"):
            from repro.launch.mesh import make_host_mesh
            self.mesh = make_host_mesh(data=config["mesh_data"])
        self.jobs: list[JobRun] = []

    def job(self, store: str, record: JobRecord):
        from repro import api
        from repro.core.store import FeatureStore
        from repro.api.sinks import StoreSink
        cfg, mix = self.config, self.mix
        j = (api.job(self.m, self.p).features(*mix["features"])
             .chunk(cfg["chunk_records"]))
        if mix["window"] == "per_file":
            j = j.window(per_file=True)
        j = (j.source(TimedSource(api.WavSource(self.data_dir), record))
             .payload(mix["payload"]))
        if mix["events"]:
            j = j.events(cfg["event_threshold_db"],
                         hysteresis_db=cfg["event_hysteresis_db"],
                         impulsive=mix["impulsive"])
        j = j.to(TimedSink(StoreSink(FeatureStore(store)), record))
        j = j.async_io()
        if self.mesh is not None:
            j = j.shards(cfg["shards"]).on(self.mesh)
        return j

    def run_one(self, name: str, deadline: float = np.inf,
                trace: bool = False) -> JobRun:
        store = os.path.join(self.work_dir, name)
        record = JobRecord(self.m.n_records, trace, deadline)
        run = JobRun(record, store)
        try:
            run.result = self.job(store, record).run()
        except WindowClosed:
            pass
        return run

    def warm_up(self) -> None:
        """One whole job: compiles (or loads) every program the window
        runs, and reads the corpus once into the page cache."""
        run = self.run_one("warm-up")
        shutil.rmtree(run.store)

    def window(self, seconds: float, trace_dir: str | None = None
               ) -> tuple[float, float]:
        """Jobs back to back for ``seconds``; returns the window's start
        and end.  With ``trace_dir``, the first job of the window runs
        whole under the profiler."""
        t0 = now()
        end = t0 + seconds
        if trace_dir is not None:
            import jax
            with programs() as self.traced_modules:
                jax.profiler.start_trace(trace_dir)
                try:
                    self.jobs.append(self.run_one("job0000", trace=True))
                finally:
                    jax.profiler.stop_trace()
        while now() < end:
            self.jobs.append(self.run_one(f"job{len(self.jobs):04d}",
                                          deadline=end))
        return t0, end

    def completed(self) -> list[JobRun]:
        return [j for j in self.jobs if j.result is not None]

    def committed_in(self, t0: float, t1: float
                     ) -> tuple[int, np.ndarray]:
        """Records committed inside [t0, t1], and their lags (s)."""
        n, lags = 0, []
        for j in self.jobs:
            for t, recs, lag in j.record.commits:
                if t0 <= t <= t1:
                    n += recs.size
                    lags.append(lag)
        return n, (np.concatenate(lags) if lags else np.zeros(0))
