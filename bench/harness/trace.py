"""The reduction from the profiler's trace of one job to the per-layer
metrics.

The trace (``jax.profiler``'s ``.xplane.pb``) holds one plane per chip
(``/device:TPU:<n>``) and one for the host.  A chip's ``XLA Ops`` line
holds every operation that ran on it, its ``XLA Modules`` line every
program; a Pallas kernel is an operation named after its
``pallas_call``.  The host plane holds the benchmark's own spans
(``bench.fetch``, ``bench.commit``, ...) on the loader's and the sink
writer's threads.  All of them are on the profiler's clock, so one
interval, from the first to the last event of the traced job, serves
every share.
"""
from __future__ import annotations

import collections
import re

import numpy as np

from . import peaks
from .window import params

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
# an XLA op event is named by its HLO text: "%welch_psd.1 = f32[8,1,256]
# {2,1,0:T(1,128)} custom-call(s16[...] ...), ..."
HLO_NAME = re.compile(r"^%?([^\s=]+)")
LAYOUT = re.compile(r"\{[^{}]*\}")


def op_name(event: str) -> str:
    """An op's instruction name: ``welch_psd.1``, ``fusion.7``."""
    return HLO_NAME.match(event).group(1)


def op_label(event: str, width: int = 120) -> str:
    """An op's instruction with its result and operand shapes, without
    layouts, cut to ``width`` characters."""
    text = event.lstrip("%")
    while LAYOUT.search(text):
        text = LAYOUT.sub("", text)
    return text[:width]


def union_s(intervals: list[tuple[float, float]], lo: float, hi: float
            ) -> float:
    """Length of the union of [start, end) intervals (ns) clipped to
    [lo, hi), in seconds."""
    total, end = 0.0, lo
    for s, e in sorted(intervals):
        s, e = max(s, end), min(e, hi)
        if e > s:
            total += e - s
            end = e
    return total / 1e9


def gaps(intervals: list[tuple[float, float]], lo: float, hi: float
         ) -> list[tuple[float, float]]:
    """The stretches of [lo, hi) that no interval covers."""
    out, end = [], lo
    for s, e in sorted(intervals):
        if s > end:
            out.append((end, min(s, hi)))
        end = max(end, e)
    if end < hi:
        out.append((end, hi))
    return [(s, e) for s, e in out if e > s]


class TracedRun:
    """What a per-layer metric reads: the traced job's device
    operations, programs and kernels per chip, and the host's spans."""

    def __init__(self, path: str, cell, modules: dict[str, str],
                 steps: int, device_kind: str, n_devices: int):
        """``modules``: the trace's names of the step and reduce
        programs; ``steps``: the traced job's steps."""
        import jax

        self.p = params(cell.config)
        self.sample_bytes = 2 if cell.mix["payload"] == "int16" else 4
        self.peak = peaks.peaks(device_kind)
        self.n_devices = n_devices
        shards = cell.config.get("shards") or 1
        self.records_per_call = cell.config["chunk_records"] * shards \
            // n_devices
        self.modules = modules
        self.steps = steps
        self.bounds: dict[str, str] = {}

        self.ops: dict[int, list] = collections.defaultdict(list)
        self.mods: dict[int, list] = collections.defaultdict(list)
        self.host: list[tuple[str, float, float]] = []
        data = jax.profiler.ProfileData.from_file(path)
        for plane in data.planes:
            dev = DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                if dev and line.name == OPS_LINE:
                    self.ops[int(dev.group(1))] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
                elif dev and line.name == MODULES_LINE:
                    self.mods[int(dev.group(1))] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
                elif not dev:
                    self.host.extend(
                        (e.name[len(SPAN_PREFIX):], e.start_ns,
                         e.start_ns + e.duration_ns)
                        for e in line.events
                        if e.name.startswith(SPAN_PREFIX))
        self.ops = {d: self.ops[d] for d in sorted(self.ops)[:self.n_devices]}
        self.mods = {d: self.mods[d] for d in self.ops}
        everything = [x for evs in (*self.ops.values(), *self.mods.values(),
                                    self.host) for x in evs]
        if not self.ops or not any(self.ops.values()):
            raise RuntimeError("the trace holds no device operation")
        self.lo = min(s for _, s, _ in everything)
        self.hi = max(e for _, _, e in everything)
        self.window_s = (self.hi - self.lo) / 1e9

    # -- device ---------------------------------------------------------
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        return float(np.mean([
            union_s([(s, e) for _, s, e in ops], self.lo, self.hi)
            for ops in self.ops.values()]))

    def device_idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s() / self.window_s)

    def _module_events(self, which: str):
        name = self.modules[which]
        return {d: [(s, e) for n, s, e in mods if n.split("(")[0] == name]
                for d, mods in self.mods.items()}

    def module_ms_per_step(self, which: str) -> float | None:
        per = [sum(e - s for s, e in evs) / 1e6 / len(evs)
               for evs in self._module_events(which).values() if evs]
        return float(np.mean(per)) if per else None

    def _kernel_events(self, kernel: str):
        pat = re.compile(rf"^{re.escape(kernel)}(\.\d+)?$")
        return {d: [(s, e) for n, s, e in ops if pat.match(op_name(n))]
                for d, ops in self.ops.items()}

    def kernel_ms_per_step(self, kernel: str) -> float | None:
        per = [sum(e - s for s, e in evs) / 1e6 / self.steps
               for evs in self._kernel_events(kernel).values() if evs]
        return float(np.mean(per)) if per else None

    def roofline_pct(self, kernel: str, work_of) -> float | None:
        """The least time of the kernel's calls on this chip over their
        summed device time, in percent; ``work_of(records, params,
        sample_bytes)`` is the work of one call."""
        calls = [evs for evs in self._kernel_events(kernel).values() if evs]
        if not calls:
            return None
        w = work_of(self.records_per_call, self.p, self.sample_bytes)
        least, bound = w.least_time(self.peak)
        n = sum(len(evs) for evs in calls)
        spent = sum(e - s for evs in calls for s, e in evs) / 1e9
        self.bounds[kernel] = bound
        return 100.0 * least * n / spent

    # -- host -----------------------------------------------------------
    def host_busy_pct(self, names: tuple[str, ...]) -> float | None:
        spans = [(s, e) for n, s, e in self.host if n in names]
        if not spans:
            return None
        return 100.0 * union_s(spans, self.lo, self.hi) / self.window_s

    def breakdown(self) -> dict:
        """The device operations that took most time (summed over chips,
        per chip), and the longest idle stretches of the first chip by
        the benchmark's host span that covered most of each."""
        total = collections.Counter()
        for ops in self.ops.values():
            for n, s, e in ops:
                total[op_label(n)] += (e - s) / 1e9
        top = [[n, t / len(self.ops)] for n, t in total.most_common(10)]
        first = next(iter(self.ops.values()))
        idle = sorted(gaps([(s, e) for _, s, e in first], self.lo, self.hi),
                      key=lambda g: g[0] - g[1])[:10]
        named = []
        for s, e in idle:
            cover = collections.Counter()
            for n, hs, he in self.host:
                cover[n] += max(0.0, min(e, he) - max(s, hs))
            what = cover.most_common(1)[0][0] if cover and \
                cover.most_common(1)[0][1] > 0 else "driver"
            named.append([what, (e - s) / 1e9])
        return {"device_ops": top, "idle_gaps": named}
