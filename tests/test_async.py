"""Pipelined executor: sync/async bitwise equivalence, AsyncSink
ordering + crash semantics, PrefetchSource behavior."""
import threading
import time

import numpy as np
import pytest

from repro import api
from repro.core.manifest import DatasetManifest, plan
from repro.core.params import DepamParams
from repro.core.store import FeatureStore

P = DepamParams(nfft=256, window_size=256, window_overlap=128,
                record_size_sec=0.25)
M = DatasetManifest(n_files=3, records_per_file=4, record_size=P.record_size,
                    fs=P.fs, seed=11)
ALL = ("welch", "spl", "tol", "percentiles")


def make_reader(m=M):
    """Deterministic per-record reader (the lineage property), shape-
    agnostic over the index array as PrefetchSource requires."""
    t = np.arange(m.record_size, dtype=np.float32) / m.fs

    def reader(idx):
        idx = np.asarray(idx)
        f0 = 40.0 + (idx.reshape(-1, 1) % 13).astype(np.float32) * 7.0
        return np.sin(2 * np.pi * f0 * t).astype(np.float32).reshape(
            *idx.shape, m.record_size)

    return reader


class TestAsyncEquivalence:
    """The acceptance contract: async results are BITWISE-identical to
    sync — pipelining reorders waiting, never computation."""

    def test_hostfed_bitwise_identical(self):
        reader = make_reader()
        sync = api.job(M, P).features(*ALL).chunk(4).source(reader).run()
        asyn = (api.job(M, P).features(*ALL).chunk(4).source(reader)
                .async_io(depth=2).run())
        for name in ALL:
            assert np.array_equal(sync[name], asyn[name]), name
        assert np.array_equal(sync["mean_welch"], asyn["mean_welch"])
        assert sync.n_records == asyn.n_records == M.n_records

    def test_device_synth_bitwise_identical(self):
        sync = api.job(M, P).features(*ALL).chunk(4).run()
        asyn = api.job(M, P).features(*ALL).chunk(4).async_io().run()
        for name in ALL:
            assert np.array_equal(sync[name], asyn[name]), name
        assert np.array_equal(sync["mean_welch"], asyn["mean_welch"])

    def test_async_resume_mid_job_bitwise(self, tmp_path):
        """Crash after 1 step under the pipelined executor, resume
        async; must equal the sync one-shot bitwise — features AND
        epoch aggregates."""
        d = str(tmp_path / "s")
        reader = make_reader()
        (api.job(M, P).features(*ALL).chunk(4).source(reader).to(d)
         .limit(1).async_io(depth=2).run())
        cur = FeatureStore(d).load_cursor()
        assert cur is not None and cur["cursor"] == 4
        resumed = (api.job(M, P).features(*ALL).chunk(4).source(reader)
                   .to(d).async_io(depth=2).run())
        oneshot = api.job(M, P).features(*ALL).chunk(4).source(reader).run()
        for name in ALL:
            assert np.array_equal(np.asarray(resumed[name]),
                                  oneshot[name]), name
        assert np.array_equal(resumed["mean_welch"], oneshot["mean_welch"])
        assert resumed.n_records == M.n_records

    def test_sync_resume_of_async_run_and_vice_versa(self, tmp_path):
        """Executor modes interoperate through the store: a job killed
        in one mode resumes in the other with identical results."""
        oneshot = api.job(M, P).features("welch", "spl").chunk(4).run()
        d1 = str(tmp_path / "a_then_s")
        api.job(M, P).features("welch", "spl").chunk(4).to(d1).limit(1) \
            .async_io().run()
        r1 = api.job(M, P).features("welch", "spl").chunk(4).to(d1).run()
        d2 = str(tmp_path / "s_then_a")
        api.job(M, P).features("welch", "spl").chunk(4).to(d2).limit(1).run()
        r2 = api.job(M, P).features("welch", "spl").chunk(4).to(d2) \
            .async_io().run()
        for r in (r1, r2):
            assert np.array_equal(np.asarray(r["welch"]), oneshot["welch"])
            assert np.array_equal(r["mean_welch"], oneshot["mean_welch"])


class RecordingSink(api.Sink):
    """Records the (op, step) sequence the worker applies."""

    wants_commit = True

    def __init__(self):
        self.events = []

    def write(self, step, indices, values):
        self.events.append(("write", step, threading.get_ident()))

    def commit(self, plan, step, agg, live):
        self.events.append(("commit", step, threading.get_ident()))


class TestAsyncSink:
    def test_strict_step_ordering_preserved(self):
        """write(k) before commit(k), steps ascending, all off the
        driver thread."""
        inner = RecordingSink()
        res = (api.job(M, P).features("spl").chunk(4).to(inner)
               .async_io().run())
        assert res.n_records == M.n_records
        ops = [(op, step) for op, step, _tid in inner.events]
        n_steps = plan(M, 1, 4).n_steps
        assert ops == [(op, s) for s in range(n_steps)
                       for op in ("write", "commit")]
        driver = threading.get_ident()
        assert all(tid != driver for _, _, tid in inner.events)

    def test_worker_error_propagates_to_driver(self):
        class FailingSink(api.Sink):
            def write(self, step, indices, values):
                raise IOError("disk full")

        with pytest.raises(RuntimeError, match="AsyncSink worker failed"):
            (api.job(M, P).features("spl").chunk(4).to(FailingSink())
             .async_io().run())

    def test_flush_blocks_until_applied(self):
        gate = threading.Event()
        applied = []

        class SlowSink(api.Sink):
            wants_commit = False

            def write(self, step, indices, values):
                gate.wait(timeout=5.0)
                applied.append(step)

        asink = api.AsyncSink(SlowSink(), queue_size=4)
        asink.open(M, P, {"spl": ()}, plan(M, 1, 4))
        asink.write(0, np.arange(4), {"spl": np.zeros(4, np.float32)})
        assert applied == []          # queued, not yet applied
        gate.set()
        asink.flush()
        assert applied == [0]
        asink.close()

    def test_crash_mid_queue_commit_never_exceeds_durable_writes(
            self, tmp_path):
        """Kill the writer with work still queued: after reopening, the
        committed cursor must only cover steps whose writes fully
        landed, and resuming completes the job bitwise-identically."""
        d = str(tmp_path / "s")
        pl_ = plan(M, 1, 4)
        release_step1 = threading.Event()

        class BlockingStoreSink(api.StoreSink):
            def write(self, step, indices, values):
                if step == 1:
                    release_step1.wait(timeout=10.0)
                super().write(step, indices, values)

        oneshot = api.job(M, P).features("welch").chunk(4).run()
        rows = {s: (pl_.step_indices(s).reshape(-1),
                    oneshot["welch"][pl_.step_indices(s).reshape(-1)])
                for s in range(3)}
        # a commit payload in the engine's own layout (zero state is
        # fine: only the per-record arrays are checked after resume)
        from repro.api import engine
        bindings, _ = engine.resolve_bindings(
            api.resolve_features(["welch"]), M, P, None)
        agg = {k: np.asarray(v, np.float64) for k, v in
               engine._init_reduce_state(bindings, None).items()
               if k != "__live__"}

        asink = api.AsyncSink(BlockingStoreSink(d), queue_size=8)
        asink.open(M, P, {"welch": (P.n_bins,)}, pl_)
        for s in range(3):
            idx, vals = rows[s]
            asink.write(s, idx, {"welch": vals})
            asink.commit(pl_, s, agg, float(4 * (s + 1)))
        # worker: write0, commit0 applied; blocked inside write1;
        # commit1..commit2 still queued -> the "crash" discards them
        deadline = time.monotonic() + 5.0
        while not FeatureStore(d).load_cursor() \
                and time.monotonic() < deadline:
            time.sleep(0.005)
        # _abort sets the kill flag first, then joins; release the gate
        # moments later so the in-flight write1 can finish dying
        threading.Timer(0.05, release_step1.set).start()
        asink._abort()

        st = FeatureStore(d)
        committed = st.committed_steps(pl_)
        assert committed == 1         # never ahead of durable writes
        on_disk = st.open_arrays({"welch": (M.n_records, P.n_bins)})
        assert np.array_equal(on_disk["welch"][rows[0][0]], rows[0][1])

        resumed = api.job(M, P).features("welch").chunk(4).to(d).run()
        assert np.array_equal(np.asarray(resumed["welch"]),
                              oneshot["welch"])

    def test_queued_commit_behind_failed_write_never_lands(self, tmp_path):
        """The worker error is sticky: once write(k) fails, the
        commit(k) already sitting in the queue must be discarded — a
        cursor must never cover data that is not on disk."""
        d = str(tmp_path / "s")
        pl_ = plan(M, 1, 4)
        gate = threading.Event()

        class FailingWriteStoreSink(api.StoreSink):
            def write(self, step, indices, values):
                gate.wait(timeout=5.0)
                raise IOError("disk full")

        asink = api.AsyncSink(FailingWriteStoreSink(d), queue_size=8)
        asink.open(M, P, {"spl": ()}, pl_)
        asink.write(0, pl_.step_indices(0).reshape(-1),
                    {"spl": np.zeros(4, np.float32)})
        asink.commit(pl_, 0, {}, 4.0)     # queued behind the doomed write
        gate.set()
        with pytest.raises(RuntimeError, match="AsyncSink worker failed"):
            asink.flush()
        with pytest.raises(RuntimeError):  # sticky through close, too
            asink.close()
        assert FeatureStore(d).committed_steps(pl_) == 0

    def test_committed_steps_flushes_pending(self, tmp_path):
        d = str(tmp_path / "s")
        pl_ = plan(M, 1, 4)
        asink = api.AsyncSink(api.StoreSink(d))
        asink.open(M, P, {"spl": ()}, pl_)
        asink.write(0, pl_.step_indices(0).reshape(-1),
                    {"spl": np.ones(4, np.float32)})
        asink.commit(pl_, 0, {}, 4.0)
        assert asink.committed_steps(pl_) == 1
        asink.close()


class TestPrefetchSource:
    def test_rejects_device_synth(self):
        with pytest.raises(ValueError, match="host-fed"):
            api.PrefetchSource(api.SynthSource())

    def test_normalizes_inner_like_as_source(self):
        src = api.PrefetchSource(make_reader(), depth=3)
        assert isinstance(src.inner, api.ReaderSource)
        assert not src.device_synth

    def test_stream_matches_inline_fetch(self):
        reader = make_reader()
        pl_ = plan(M, 2, 3)
        inline = api.ReaderSource(reader)
        pre = api.PrefetchSource(reader, depth=2, overdecompose=3)
        got = list(pre.stream(pl_, 1, pl_.n_steps))
        want = list(inline.stream(pl_, 1, pl_.n_steps))
        assert len(got) == len(want) == pl_.n_steps - 1
        for step, (g, w) in enumerate(zip(got, want), start=1):
            assert np.array_equal(g, w)
            assert g.shape[:-1] == pl_.step_indices(step).shape

    def test_double_wrap_is_not_applied_by_builder(self):
        """async_io() must not re-wrap an explicit PrefetchSource."""
        pre = api.PrefetchSource(make_reader(), depth=4, workers=2)
        j = api.job(M, P).features("spl").chunk(4).source(pre).async_io()
        res = j.run()
        assert res.n_records == M.n_records
        sync = api.job(M, P).features("spl").chunk(4) \
            .source(make_reader()).run()
        assert np.array_equal(res["spl"], sync["spl"])
