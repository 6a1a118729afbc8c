"""The program's own spans in a chip trace (``data/set1.full.spans.*``:
four steps of a ``set1.full`` job on one TPU v5e, recorded with
``record_trace.py set1.full`` and the benchmark's spans on), and the
per-layer numbers of ``bench/harness/program_spans.py``."""
import json
import math
import pathlib

import pytest

from bench.harness import program_spans
from bench.harness.spec import Cell
from bench.harness.trace import TracedRun

DATA = pathlib.Path(__file__).parent / "data"
# within this much, the host's and the device's timestamps agree
CLOCK_NS = 100_000


def traced_run(name: str) -> TracedRun:
    meta = json.loads((DATA / f"{name}.json").read_text())
    return TracedRun(str(DATA / f"{name}.xplane.pb"), Cell("set1.full"),
                     meta["modules"], meta["steps"], meta["device_kind"], 1)


@pytest.fixture(scope="module")
def spans():
    run = traced_run("set1.full.spans")
    return program_spans.ProgramSpans(
        str(DATA / "set1.full.spans.xplane.pb"), run)


# what each accepted per-layer metric reads on ``set1.full.xplane.pb``,
# a trace without the program's spans; those spans may not move any
PINNED = {
    "read_busy_pct": 11.393937624059147,
    "sink_busy_pct": 4.645866317102228,
    "device_idle_pct": 14.344197968747874,
    "step_device_ms": 25.0534605,
    "reduce_device_ms": 140.91909625,
    "welch_psd_roofline": 3.553299708809066,
    "frame_psd_roofline": 9.34012739431989,
    "kernel_ms.detect_events": 2.59072675,
}


@pytest.mark.parametrize("metric", sorted(PINNED))
def test_accepted_metrics_read_as_before(metric):
    run = traced_run("set1.full")
    assert Cell("set1.full").reader(metric)(run) == PINNED[metric]
    assert (run.lo, run.hi) == (61160655.0, 836217373.0)


def test_program_spans_leave_the_window(spans):
    """The window is set by device events and ``bench.*`` spans only;
    the program's spans are clipped to it."""
    run = spans.run
    bench = [s for s in spans.all_spans if s.name.startswith("bench.")]
    device = [x for evs in (*run.ops.values(), *run.mods.values())
              for x in evs]
    assert run.lo == min([s.start for s in bench] + [s for _, s, _ in device])
    assert run.hi == max([s.end for s in bench] + [e for _, _, e in device])
    assert any(s.name.startswith("depam.") for s in spans.all_spans)
    assert all(s.end > run.lo and s.start < run.hi for s in spans.spans)


def test_host_and_device_share_a_clock(spans):
    """Each step's dispatch starts before its step program does on the
    chip; its d2h wait ends after its reduce program does."""
    run = spans.run
    step_mods = sorted(run._module_events("step")[0])
    reduce_mods = sorted(run._module_events("reduce")[0])
    dispatch = sorted(spans.named("dispatch"), key=lambda s: s.stats["step"])
    waits = sorted(spans.named("d2h_wait"), key=lambda s: s.stats["step"])
    assert len(step_mods) == len(reduce_mods) == len(dispatch) \
        == len(waits) == run.steps
    for d, (s, _) in zip(dispatch, step_mods):
        assert d.start <= s + CLOCK_NS
    for w, (_, e) in zip(waits, reduce_mods):
        assert w.end + CLOCK_NS >= e


@pytest.mark.parametrize("metric", ["fetch_wait_ms", "dispatch_ms",
                                    "d2h_wait_ms", "sink_put_ms",
                                    "fsync_ms", "commit_mb_per_step"])
def test_six_readers_are_finite(spans, metric):
    value = program_spans.metrics(spans)[metric]
    assert value is not None and math.isfinite(value) and value >= 0


def test_commit_bytes_repeat_exactly(spans):
    sizes = {s.stats["bytes"] for s in spans.named("store.commit")}
    assert len(sizes) == 1 and sizes.pop() > 0


@pytest.mark.parametrize("prefix, names", [
    ("read", ("fetch",)),
    ("sink.", ("write", "write_windows", "write_events", "commit"))])
def test_twins_of_the_benchmark_spans(spans, prefix, names):
    """The union of the program's read (or writer-thread) spans agrees
    with the benchmark's spans around the same calls."""
    ours = spans.busy_pct(prefix)
    theirs = spans.run.host_busy_pct(names)
    assert abs(ours - theirs) <= 1.0


def test_idle_gaps_are_named_by_program_spans(spans):
    gaps = spans.idle_gaps()
    assert 0 < len(gaps) <= 10
    assert any(name.startswith("depam.") for name, _ in gaps)
    assert sum(name == "driver" for name, _ in gaps) <= 2


def test_self_time_gives_each_instant_to_the_innermost_span():
    S = program_spans.Span
    outer = S("depam.drain", 0, 10, {}, (0, 0))
    inner = S("depam.d2h_wait", 2, 5, {}, (0, 0))
    other = S("bench.fetch", 1, 4, {}, (0, 1))
    assert sorted(program_spans.self_time([outer, inner, other])) == [
        ("bench.fetch", 1, 4), ("depam.d2h_wait", 2, 5),
        ("depam.drain", 0, 2), ("depam.drain", 5, 10)]
