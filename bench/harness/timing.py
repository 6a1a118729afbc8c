"""Pass-through wrappers around the calls into the input and the sink
layers of one job, and the record of what they saw.

``TimedSource`` wraps the job's ``WavSource``; the job builder then puts
its own ``PrefetchSource`` around it, so the program's read-ahead is
unchanged and the wrapper sees each read task on the loader's threads:
it notes when each record was first asked for.  ``TimedSink`` is handed
to ``.to()``; the builder's ``AsyncSink`` wraps it, so it sees the
writer thread's calls into the store: it notes when each commit
returned, and so each committed record's lag.  Neither touches a byte:
the job's outputs are the same with and without them.

Under ``trace=True`` every wrapped call is also a ``TraceAnnotation``
(``bench.fetch``, ``bench.commit``, ...), so the profiler's trace shows
it on the host's clock.
"""
from __future__ import annotations

import contextlib
import threading
import time

import numpy as np

from repro.api.sinks import Sink
from repro.api.sources import Source

now = time.perf_counter


class WindowClosed(Exception):
    """Raised by a read that starts after the window closed: it ends the
    job that was running at the close, whose later work counts for
    nothing."""


class JobRecord:
    """Timings of one job: when each record was first asked for, and
    each commit.  A read that starts after ``deadline`` raises
    :class:`WindowClosed`."""

    def __init__(self, n_records: int, trace: bool = False,
                 deadline: float = np.inf):
        self.trace = trace
        self.deadline = deadline
        self.first_fetch = np.full(n_records, np.inf)
        # (time the commit returned, records it covered, their lags s)
        self.commits: list[tuple[float, np.ndarray, np.ndarray]] = []
        self._lock = threading.Lock()

    def span(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(f"bench.{name}")

    def fetched(self, indices: np.ndarray, t: float) -> None:
        idx = np.asarray(indices).reshape(-1)
        idx = idx[(idx >= 0) & (idx < self.first_fetch.size)]
        with self._lock:
            np.minimum.at(self.first_fetch, idx, t)

    def committed(self, records: np.ndarray, t: float) -> None:
        with self._lock:
            self.commits.append(
                (t, records, t - self.first_fetch[records]))


class TimedSource(Source):
    """Notes when each record is first asked for, and spans every
    ``fetch`` of the wrapped host-fed source."""

    def __init__(self, inner: Source, record: JobRecord):
        self.inner = inner
        self.record = record

    @property
    def payload_dtype(self) -> str:
        return self.inner.payload_dtype

    @property
    def device_synth(self) -> bool:
        return self.inner.device_synth

    def with_payload(self, dtype: str) -> "TimedSource":
        if dtype == self.payload_dtype:
            return self
        return TimedSource(self.inner.with_payload(dtype), self.record)

    def bind(self, m, p) -> "TimedSource":
        self.inner = self.inner.bind(m, p)
        return self

    def fetch(self, indices: np.ndarray) -> np.ndarray:
        t0 = now()
        if t0 > self.record.deadline:
            raise WindowClosed
        self.record.fetched(indices, t0)
        with self.record.span("fetch"):
            return self.inner.fetch(indices)

    def scales(self, indices: np.ndarray) -> np.ndarray:
        return self.inner.scales(indices)

    def poll(self, indices: np.ndarray) -> str:
        return self.inner.poll(indices)

    def stream_end(self):
        return self.inner.stream_end()

    def close(self) -> None:
        self.inner.close()


class TimedSink(Sink):
    """Spans ``write``, ``write_windows``, ``write_events`` and
    ``commit`` of the wrapped sink, and times the lag of each committed
    record from its first read."""

    def __init__(self, inner: Sink, record: JobRecord):
        self.inner = inner
        self.record = record
        self.resumable = inner.resumable
        self.wants_commit = inner.wants_commit


    def open(self, m, p, shapes, plan):
        self.inner.open(m, p, shapes, plan)

    def set_instrument(self, instrument):
        self.inner.set_instrument(instrument)

    def open_window_edges(self, edges):
        self.inner.open_window_edges(edges)

    def open_windows(self, shapes):
        self.inner.open_windows(shapes)

    def open_events(self, layouts):
        self.inner.open_events(layouts)

    def describe(self):
        return self.inner.describe()

    def resume_state(self):
        return self.inner.resume_state()

    def committed_steps(self, plan) -> int:
        return self.inner.committed_steps(plan)

    def committed_plan(self):
        return self.inner.committed_plan()

    def write(self, step, indices, values):
        with self.record.span("write"):
            self.inner.write(step, indices, values)

    def write_windows(self, name, start, values):
        with self.record.span("write_windows"):
            self.inner.write_windows(name, start, values)

    def write_events(self, step, indices, values):
        with self.record.span("write_events"):
            self.inner.write_events(step, indices, values)

    def commit(self, plan, step, agg, live):
        with self.record.span("commit"):
            self.inner.commit(plan, step, agg, live)
        t = now()
        idx = np.asarray(plan.step_indices(step)).reshape(-1)
        live_mask = np.asarray(plan.step_mask(step)).reshape(-1)
        self.record.committed(idx[live_mask], t)

    def event_result(self):
        return self.inner.event_result()

    def result(self):
        return self.inner.result()

    def close(self):
        self.inner.close()
