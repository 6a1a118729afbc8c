"""The program's own host spans in a traced job, and the per-layer
numbers they give.

The program marks each host boundary of its batch path with a
``depam.<name>`` span (``repro.core.spans``) whose stats carry the plan
step and the bytes or records moved.  They sit on the host plane of the
profiler's trace, beside the benchmark's ``bench.*`` spans and on the
same clock as the device planes.  They are read here on their own: the
traced window, ``TracedRun.lo`` to ``TracedRun.hi``, stays set by the
device events and the benchmark's spans alone, and the program's spans
are clipped to it.

``metrics()`` gives the six numbers, ``ProgramSpans.idle_gaps()`` names
each long idle stretch of the first chip by the span with the most self
time in it.
"""
from __future__ import annotations

import collections
import dataclasses

from .trace import TracedRun, gaps, union_s

PROGRAM = "depam."
BENCH = "bench."


@dataclasses.dataclass(frozen=True)
class Span:
    name: str            # with its prefix: "depam.dispatch", "bench.fetch"
    start: float         # ns, on the profiler's clock
    end: float
    stats: dict
    thread: tuple[int, int]   # (plane, line) of the trace


def read_spans(path: str) -> list[Span]:
    """Every ``depam.*`` and ``bench.*`` event of the trace's host
    planes."""
    import jax
    out = []
    data = jax.profiler.ProfileData.from_file(path)
    for p, plane in enumerate(data.planes):
        if not plane.name.startswith("/host:"):
            continue
        for t, line in enumerate(plane.lines):
            out.extend(Span(e.name, e.start_ns, e.start_ns + e.duration_ns,
                            {k: v for k, v in e.stats}, (p, t))
                       for e in line.events
                       if e.name.startswith((PROGRAM, BENCH)))
    return out


def self_time(spans: list[Span]) -> list[tuple[str, float, float]]:
    """Cuts each thread's spans into (name, start, end) pieces, each
    given to the innermost span open there: a span's self time is its
    interval less what its children on the same thread cover."""
    by_thread = collections.defaultdict(list)
    for s in spans:
        by_thread[s.thread].append(s)
    out = []
    for evs in by_thread.values():
        stack: list[Span] = []
        at = 0.0
        for s in sorted(evs, key=lambda s: (s.start, -s.end)):
            while stack and stack[-1].end <= s.start:
                done = stack.pop()
                out.append((done.name, at, done.end))
                at = done.end
            if stack:
                out.append((stack[-1].name, at, s.start))
            stack.append(s)
            at = s.start
        while stack:
            done = stack.pop()
            out.append((done.name, at, done.end))
            at = done.end
    return [(n, s, e) for n, s, e in out if e > s]


class ProgramSpans:
    """The ``depam.*`` spans of a traced job, clipped to ``run``'s
    window, per step of ``run``."""

    def __init__(self, path: str, run: TracedRun):
        self.run = run
        self.all_spans = read_spans(path)
        self.spans = [s for s in self.all_spans
                      if s.name.startswith(PROGRAM)
                      and s.start < run.hi and s.end > run.lo]

    def named(self, name: str) -> list[Span]:
        return [s for s in self.spans if s.name == PROGRAM + name]

    def ms(self, name: str) -> float | None:
        """Summed time of the span ``depam.<name>`` inside the window."""
        evs = self.named(name)
        if not evs:
            return None
        lo, hi = self.run.lo, self.run.hi
        return sum(min(s.end, hi) - max(s.start, lo) for s in evs) / 1e6

    def ms_per_step(self, name: str) -> float | None:
        total = self.ms(name)
        return None if total is None else total / self.run.steps

    def arg_sum(self, name: str, arg: str) -> float | None:
        """Summed stat ``arg`` of the spans ``depam.<name>`` that
        overlap the window."""
        evs = [s for s in self.named(name) if arg in s.stats]
        return sum(s.stats[arg] for s in evs) if evs else None

    def busy_pct(self, prefix: str) -> float | None:
        """Share of the window inside at least one span whose name
        starts with ``depam.<prefix>`` (the union over threads), in
        percent."""
        evs = [(s.start, s.end) for s in self.spans
               if s.name.startswith(PROGRAM + prefix)]
        if not evs:
            return None
        return 100.0 * union_s(evs, self.run.lo, self.run.hi) \
            / self.run.window_s

    def idle_gaps(self, n: int = 10) -> list[list]:
        """The ``n`` longest stretches in which no operation ran on the
        first chip, longest first, each as [name, seconds]: the
        ``bench.*`` or ``depam.*`` span with the most self time in the
        stretch, summed over the host's threads, or ``driver`` where no
        span covers it."""
        run = self.run
        first = next(iter(run.ops.values()))
        idle = sorted(gaps([(s, e) for _, s, e in first], run.lo, run.hi),
                      key=lambda g: g[0] - g[1])[:n]
        pieces = self_time(self.all_spans)
        named = []
        for s, e in idle:
            cover = collections.Counter()
            for name, ps, pe in pieces:
                if pe > s and ps < e:
                    cover[name] += min(e, pe) - max(s, ps)
            best = cover.most_common(1)
            named.append([best[0][0] if best else "driver", (e - s) / 1e9])
        return named


def metrics(spans: ProgramSpans) -> dict[str, float | None]:
    """The per-layer numbers of the program's spans, by metric name:
    the driver's waits and dispatch per step, the fsyncs' time and the
    carry sidecar's megabytes per commit."""
    commits = len(spans.named("store.commit"))
    fsync = spans.ms("store.fsync")
    sidecar = spans.arg_sum("store.commit", "bytes")
    return {
        "fetch_wait_ms": spans.ms_per_step("fetch_wait"),
        "dispatch_ms": spans.ms_per_step("dispatch"),
        "d2h_wait_ms": spans.ms_per_step("d2h_wait"),
        "sink_put_ms": spans.ms_per_step("sink_put"),
        "fsync_ms": None if fsync is None else fsync / commits,
        "commit_mb_per_step": None if sidecar is None
        else sidecar / commits / 1e6,
    }
