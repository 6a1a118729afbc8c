"""The program's host spans (``repro.core.spans``) in a profiler trace
of a small pipelined job with events and a resumable store: one span of
each step-scoped kind per plan step, the commit's byte count and its
fsyncs, and outputs bitwise-equal to the same job run untraced."""
import collections
import glob
import os

import jax
import numpy as np
import pytest

from repro import api
from repro.core.manifest import DatasetManifest
from repro.api.features import selects_order_stats
from repro.core.params import PARAM_SET_1, DepamParams
from repro.core.store import FeatureStore
from repro.data.wavio import write_dataset

P = DepamParams(nfft=256, window_size=256, window_overlap=128,
                record_size_sec=0.25)
M = DatasetManifest(n_files=3, records_per_file=4, record_size=P.record_size,
                    fs=P.fs, seed=11)
CHUNK = 4
N_STEPS = M.n_records // CHUNK
PER_STEP = ("fetch_wait", "dispatch", "drain", "store.commit")
ALL_SPANS = {"start", "fetch_wait", "dispatch", "drain", "d2h_wait",
             "compact", "flush_windows", "sink_put", "sink.write",
             "sink.write_events", "sink.write_windows", "sink.commit",
             "store.commit", "store.fsync", "read"}

Span = collections.namedtuple("Span", "name start end stats thread")


def job(root, store):
    return (api.job(M, P).features("welch", "spl", "ltsa").chunk(CHUNK)
            .window(per_file=True).source(api.WavSource(root))
            .events(-25.5, hysteresis_db=0.5).to(store).async_io(depth=2))


def read_spans(trace_dir) -> list[Span]:
    """Every ``depam.*`` host event of the trace, named without the
    prefix; ``thread`` tells the host's lines apart."""
    pb, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for p, plane in enumerate(jax.profiler.ProfileData.from_file(pb).planes):
        for t, line in enumerate(plane.lines):
            out.extend(Span(e.name[len("depam."):], e.start_ns,
                            e.start_ns + e.duration_ns,
                            {k: v for k, v in e.stats}, (p, t))
                       for e in line.events if e.name.startswith("depam."))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("spans")
    root = str(tmp / "wavs")
    write_dataset(root, M)
    with jax.profiler.trace(str(tmp / "trace")):
        result = job(root, str(tmp / "traced")).run()
    return result, read_spans(tmp / "trace"), str(tmp / "traced"), root


def test_every_span_of_the_batch_path_fires(traced):
    _, spans, _, _ = traced
    assert {s.name for s in spans} == ALL_SPANS


@pytest.mark.parametrize("name", PER_STEP)
def test_one_span_per_plan_step(traced, name):
    _, spans, _, _ = traced
    steps = sorted(s.stats["step"] for s in spans if s.name == name)
    assert steps == list(range(N_STEPS))


def test_commit_bytes_are_the_committed_sidecar(traced):
    _, spans, store, _ = traced
    last = max((s for s in spans if s.name == "store.commit"),
               key=lambda s: s.stats["step"])
    cursor = FeatureStore(store).load_cursor()
    assert cursor["step"] == last.stats["step"] == N_STEPS - 1
    size = os.path.getsize(os.path.join(store, cursor["agg_file"]))
    assert last.stats["bytes"] == size > 0


def test_three_fsyncs_under_each_commit(traced):
    """The event log, the carry sidecar and the cursor."""
    _, spans, _, _ = traced
    fsyncs = [s for s in spans if s.name == "store.fsync"]
    commits = [s for s in spans if s.name == "store.commit"]
    for c in commits:
        inside = [f for f in fsyncs if f.thread == c.thread
                  and c.start <= f.start and f.end <= c.end]
        assert len(inside) == 3
    assert len(fsyncs) == 3 * len(commits)


def test_drain_holds_its_waits(traced):
    """``d2h_wait`` and ``compact`` nest in the same step's ``drain``;
    the sink's own calls run on another thread than the driver's."""
    _, spans, _, _ = traced
    drains = {s.stats["step"]: s for s in spans if s.name == "drain"}
    for s in spans:
        if s.name in ("d2h_wait", "compact"):
            d = drains[s.stats["step"]]
            assert d.thread == s.thread
            assert d.start <= s.start and s.end <= d.end
    driver = {s.thread for s in spans if s.name == "dispatch"}
    writer = {s.thread for s in spans if s.name.startswith("sink.")}
    assert len(driver) == len(writer) == 1 and driver != writer


def test_traced_outputs_equal_untraced(traced, tmp_path):
    got, _, _, root = traced
    want = job(root, str(tmp_path / "untraced")).run()
    for ns in ("features", "epoch", "windows"):
        a, b = getattr(got, ns), getattr(want, ns)
        assert sorted(a) == sorted(b)
        for k in a:
            assert np.array_equal(a[k], b[k]), (ns, k)
    for k in want.events:
        assert np.array_equal(got.events[k].counts, want.events[k].counts)
        assert np.array_equal(got.events[k].rows, want.events[k].rows)


@pytest.mark.parametrize("params, selects", [
    (PARAM_SET_1, 1),
    (P, 0),
], ids=["set1_frames", "below_crossover"])
def test_start_reports_the_percentile_path(tmp_path, params, selects):
    """``start`` carries ``pct_select``: 1 when the compiled step selects
    the percentiles' order statistics (set 1's 15,359 frames a record),
    0 when it sorts them (the 63 frames of quarter-second records)."""
    m = DatasetManifest(n_files=1, records_per_file=2,
                        record_size=params.record_size, fs=params.fs, seed=3)
    with jax.profiler.trace(str(tmp_path)):
        api.job(m, params).features("percentiles").chunk(2).run()
    start, = [s for s in read_spans(tmp_path) if s.name == "start"]
    assert start.stats["pct_select"] == selects
    assert selects_order_stats(params.frames_per_record) == bool(selects)
