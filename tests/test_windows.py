"""Multi-resolution reduction API: windows, the Reduction protocol,
windowed built-ins (ltsa/spd/minmax) vs NumPy oracles, resume/executor/
payload bitwise matrix, builder validation, JobResult namespaces.

The property-based class skips without hypothesis (an optional dev
dependency); everything else always runs.
"""
import tempfile

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # stubs so decorators at class-body time work
    HAVE_HYPOTHESIS = False

    def given(*a, **k):
        return lambda f: f

    def settings(*a, **k):
        return lambda f: f

    class _St:
        """Chainable stub so strategy expressions (incl. .filter/.map)
        evaluate at class-body time when hypothesis is absent."""

        def __call__(self, *a, **k):
            return self

        def __getattr__(self, name):
            return self

    st = _St()

needs_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS,
    reason="optional dev dependency: pip install hypothesis")

import jax
import jax.numpy as jnp

from repro import api
from repro.api import features
from repro.core import spectra
from repro.core.manifest import DatasetManifest
from repro.core.params import DepamParams
from repro.core.store import FeatureStore

P = DepamParams(nfft=256, window_size=256, window_overlap=128,
                record_size_sec=0.25)
M = DatasetManifest(n_files=3, records_per_file=4, record_size=P.record_size,
                    fs=P.fs, seed=11)
WINDOWED = ("ltsa", "spd", "min_welch", "max_welch")


def window_slices(edges):
    return list(zip(edges[:-1], edges[1:]))


def frame_db_oracle(m, p):
    """(n_records, n_frames, n_bins) dB spectrogram via the XLA path."""
    recs = jnp.stack([api.sources.synth_record(jnp.int32(i), m)
                      for i in range(m.n_records)])
    fp = np.asarray(spectra.frame_psd(recs, p))
    return 10.0 * np.log10(np.maximum(fp, 1e-30)) + p.gain_db


def spd_oracle(db, edges):
    """np.histogram(density=True) per (window, freq bin) — pypam
    compute_spd semantics."""
    bins = np.arange(api.SPD_DB_MIN,
                     api.SPD_DB_MAX + api.SPD_DB_STEP / 2, api.SPD_DB_STEP)
    out = np.zeros((len(edges) - 1, db.shape[-1], api.SPD_N_DB))
    for w, (lo, hi) in enumerate(window_slices(edges)):
        for b in range(db.shape[-1]):
            vals = db[lo:hi, :, b].ravel()
            if len(vals) and ((vals >= bins[0]) & (vals < bins[-1])).any():
                out[w, b] = np.histogram(vals, bins=bins, density=True)[0]
    return out


class TestWindow:
    def test_edges_and_ids(self):
        w = api.Window("records", records=5)
        assert w.edges(M).tolist() == [0, 5, 10, 12]
        assert w.n_windows(M) == 3
        assert w.ids(np.arange(14), M).tolist() == \
            [0, 0, 0, 0, 0, 1, 1, 1, 1, 1, 2, 2, 2, 2]  # padding clamps

    def test_per_file_follows_manifest_offsets(self):
        m = DatasetManifest.from_files((2, 0, 3), record_size=64, fs=100.0)
        w = api.Window("file")
        assert w.edges(m).tolist() == [0, 2, 2, 5]
        assert w.ids(np.asarray([0, 1, 2, 3, 4]), m).tolist() == \
            [0, 0, 2, 2, 2]        # the empty file owns no records

    def test_epoch_is_degenerate(self):
        assert api.EPOCH_WINDOW.n_windows(M) == 1
        assert api.EPOCH_WINDOW.ids(np.arange(5), M).tolist() == [0] * 5

    def test_invalid_windows_raise(self):
        with pytest.raises(ValueError, match="records"):
            api.Window("records")
        with pytest.raises(ValueError, match=">= 1"):
            api.Window("records", records=0)
        with pytest.raises(ValueError, match="kind"):
            api.Window("hourly")


class TestWindowedOracle:
    """ltsa/minmax/spd against NumPy reductions of the same run's
    per-record arrays (and the XLA frame spectrogram for spd)."""

    @pytest.fixture(scope="class")
    def res(self):
        return (api.job(M, P)
                .features("welch", "ltsa", "spd", "minmax")
                .window(records=5).chunk(4).kernels(False).run())

    def test_shapes_and_edges(self, res):
        assert set(res.windows) == set(WINDOWED)
        assert res.windows["ltsa"].shape == (3, P.n_bins)
        assert res.windows["spd"].shape == (3, P.n_bins, api.SPD_N_DB)
        assert res.window_edges["ltsa"].tolist() == [0, 5, 10, 12]

    def test_ltsa_is_windowed_mean_welch(self, res):
        w = res["welch"].astype(np.float64)
        for i, (lo, hi) in enumerate(
                window_slices(res.window_edges["ltsa"])):
            assert np.allclose(res["ltsa"][i], w[lo:hi].mean(0), rtol=1e-6)

    def test_minmax_are_exact_extrema(self, res):
        w = res["welch"]
        for i, (lo, hi) in enumerate(
                window_slices(res.window_edges["min_welch"])):
            assert np.array_equal(res["min_welch"][i], w[lo:hi].min(0))
            assert np.array_equal(res["max_welch"][i], w[lo:hi].max(0))

    def test_spd_matches_numpy_histogram(self, res):
        db = frame_db_oracle(M, P)
        want = spd_oracle(db, res.window_edges["spd"])
        assert np.allclose(res["spd"], want, atol=1e-7)
        # each (window, freq) density integrates to 1 over dB
        mass = res["spd"].sum(-1) * api.SPD_DB_STEP
        assert np.allclose(mass, 1.0, atol=1e-5)

    def test_epoch_window_is_the_default(self):
        one = (api.job(M, P).features("welch", "ltsa").chunk(4)
               .kernels(False).run())
        assert one.windows["ltsa"].shape == (1, P.n_bins)
        assert np.allclose(one.windows["ltsa"][0],
                           one["mean_welch"], rtol=1e-6)

    def test_per_file_empty_window_is_nan(self):
        m = DatasetManifest.from_files((3, 0, 4), record_size=P.record_size,
                                       fs=P.fs, seed=5)
        res = (api.job(m, P).features("welch", "ltsa", "minmax")
               .window(per_file=True).chunk(4).kernels(False).run())
        assert np.isnan(res.windows["ltsa"][1]).all()
        assert np.isnan(res.windows["min_welch"][1]).all()
        w = res["welch"].astype(np.float64)
        assert np.allclose(res.windows["ltsa"][0], w[:3].mean(0), rtol=1e-6)
        assert np.allclose(res.windows["ltsa"][2], w[3:].mean(0), rtol=1e-6)


class TestExecutorPayloadMatrix:
    """The acceptance contract: windowed outputs are bitwise-identical
    across {sync, async} x {fresh, mid-window resume} x {float32, int16
    payload}."""

    @pytest.fixture(scope="class")
    def wav_root(self, tmp_path_factory):
        root = tmp_path_factory.mktemp("wavs")
        from repro.data.wavio import write_dataset
        write_dataset(str(root), M)
        return str(root)

    def job(self, wav_root, payload):
        # window(records=5) with chunk 4: step boundaries fall
        # mid-window, so every resume below restores a partial carry
        return (api.job(M, P).features("welch", "ltsa", "spd", "minmax")
                .window(records=5).chunk(4)
                .source(api.WavSource(wav_root)).payload(payload))

    @pytest.fixture(scope="class")
    def reference(self, wav_root):
        return self.job(wav_root, "float32").run()

    @pytest.mark.parametrize("payload", ["float32", "int16"])
    @pytest.mark.parametrize("asyn", [False, True])
    @pytest.mark.parametrize("resume", [False, True])
    def test_bitwise(self, wav_root, reference, payload, asyn, resume):
        with tempfile.TemporaryDirectory() as d:
            def build():
                j = self.job(wav_root, payload)
                j = j.async_io(depth=2) if asyn else j
                return j.to(d)
            if resume:
                build().limit(1).run()     # crash mid-window (cursor 4)
                assert FeatureStore(d).load_cursor()["cursor"] == 4
            res = build().run()
        for name in WINDOWED:
            assert np.array_equal(res.windows[name],
                                  reference.windows[name]), name
        assert np.array_equal(res["welch"], reference["welch"])
        assert np.array_equal(res["mean_welch"], reference["mean_welch"])


class TestStoreLayout:
    def test_window_arrays_ride_the_store(self, tmp_path):
        d = str(tmp_path / "s")
        res = (api.job(M, P).features("welch", "ltsa", "spd")
               .window(records=5).chunk(4).to(d).run())
        st = FeatureStore(d)
        on_disk = st.open_arrays({
            "ltsa": (3, P.n_bins), "spd": (3, P.n_bins, api.SPD_N_DB)},
            extend=True)
        assert np.array_equal(on_disk["ltsa"], res.windows["ltsa"])
        assert np.array_equal(on_disk["spd"], res.windows["spd"])

    def test_closed_windows_flush_before_their_commit(self, tmp_path):
        """A window whose records are fully committed must be readable
        from the store even if the job dies right after that commit."""
        d = str(tmp_path / "s")
        # chunk 4, window 4: step k closes window k exactly
        (api.job(M, P).features("welch", "ltsa").window(records=4)
         .chunk(4).to(d).limit(2).run())     # die after 2 of 3 steps
        full = (api.job(M, P).features("welch", "ltsa").window(records=4)
                .chunk(4).run())
        st = FeatureStore(d)
        rows = st.open_arrays({"ltsa": (3, P.n_bins)}, extend=True)["ltsa"]
        assert np.array_equal(rows[:2], full.windows["ltsa"][:2])

    def test_resume_with_changed_window_fails_loudly(self, tmp_path):
        d = str(tmp_path / "s")
        (api.job(M, P).features("welch", "ltsa").window(records=5)
         .chunk(4).to(d).limit(1).run())
        with pytest.raises(ValueError, match="cannot resume"):
            (api.job(M, P).features("welch", "ltsa").window(records=4)
             .chunk(4).to(d).run())
        with pytest.raises(ValueError, match="cannot resume"):
            (api.job(M, P).features("welch", "ltsa", "minmax")
             .window(records=5).chunk(4).to(d).run())

    def test_callback_sink_streams_windows(self):
        seen = []
        sink = api.CallbackSink(lambda step, idx, vals: None,
                                on_windows=lambda name, start, vals:
                                seen.append((name, start, len(vals))))
        (api.job(M, P).features("ltsa").window(records=4).chunk(4)
         .to(sink).run())
        assert ("ltsa", 0, 1) in seen      # closed windows stream early
        got = sorted((s, s + n) for name, s, n in seen)
        covered = set()
        for lo, hi in got:
            covered |= set(range(lo, hi))
        assert covered == {0, 1, 2}


class TestBuilderValidation:
    def test_payload_on_device_synth_raises_at_entry(self):
        with pytest.raises(ValueError, match="device-synthesized"):
            api.job(M, P).features("welch").payload("int16").run()

    def test_raw_reader_float_conflict_surfaces_at_entry(self):
        raw = api.ReaderSource(lambda idx: np.zeros(
            (*idx.shape, M.record_size), np.int16), payload_dtype="int16")
        with pytest.raises(ValueError, match="raw-int16"):
            api.job(M, P).features("welch").source(raw) \
                .payload("float32").run()

    def test_duplicate_reduction_output_raises(self):
        clash = api.FeatureSpec(
            name="ltsa2", shape=None, compute=lambda ctx: ctx.welch,
            reductions=(api.mean_reduction(
                "ltsa", lambda m, p: p.n_bins),))
        with pytest.raises(ValueError, match="declared by both"):
            api.job(M, P).features("ltsa", clash).run()

    def test_reduction_output_shadowing_feature_raises(self):
        shadow = api.FeatureSpec(
            name="aux", shape=None, compute=lambda ctx: ctx.welch,
            reductions=(api.mean_reduction(
                "welch", lambda m, p: p.n_bins),))
        with pytest.raises(ValueError, match="collides"):
            api.job(M, P).features("welch", shadow).run()

    def test_window_knob_validation(self):
        with pytest.raises(ValueError, match="mutually exclusive"):
            api.job(M, P).window(records=4, per_file=True)
        with pytest.raises(ValueError, match=">= 1"):
            api.job(M, P).window(records=0)
        with pytest.raises(ValueError, match="chunk"):
            api.job(M, P).chunk(0)


class TestJobResultNamespaces:
    def test_ambiguous_name_raises(self):
        r = api.JobResult(features={"x": np.zeros(2)},
                          epoch={}, windows={"x": np.zeros((1, 2))},
                          window_edges={}, n_records=2, plan=None)
        with pytest.raises(KeyError, match="ambiguous"):
            r["x"]
        assert r.windows["x"].shape == (1, 2)   # explicit access works

    def test_lookup_covers_all_three_namespaces(self):
        res = (api.job(M, P).features("welch", "spl", "ltsa")
               .window(records=5).chunk(4).run())
        assert res["spl"].shape == (M.n_records,)          # features
        assert res["mean_welch"].shape == (P.n_bins,)      # epoch
        assert res["ltsa"].shape == (3, P.n_bins)          # windows
        with pytest.raises(KeyError, match="not in features"):
            res["nope"]


@needs_hypothesis
class TestWindowedProperties:
    """Every windowed reduction against its NumPy oracle across random
    manifest layouts, window resolutions, chunkings (padding masks), and
    mid-window resume points — the space fixed cases cannot cover."""

    @settings(max_examples=8, deadline=None)
    @given(file_counts=st.lists(st.integers(0, 5), min_size=1, max_size=4)
           .filter(lambda fc: sum(fc) >= 1),
           wsel=st.one_of(st.integers(1, 7),
                          st.sampled_from(["file", "epoch"])),
           chunk=st.integers(1, 5),
           resume_steps=st.integers(0, 3))
    def test_windowed_reductions_match_numpy(self, file_counts, wsel,
                                             chunk, resume_steps):
        m = DatasetManifest.from_files(file_counts,
                                       record_size=P.record_size,
                                       fs=P.fs, seed=23)

        def build(sink=None, limit=None):
            j = (api.job(m, P).features("welch", "ltsa", "spd", "minmax")
                 .chunk(chunk).kernels(False).to(sink).limit(limit))
            if wsel == "file":
                return j.window(per_file=True)
            if wsel == "epoch":
                return j.window()
            return j.window(records=wsel)

        res = build().run()
        edges = res.window_edges["ltsa"]
        assert edges[-1] == m.n_records

        # ---- oracles from the same run's per-record welch ----
        w64 = res["welch"].astype(np.float64)
        for i, (lo, hi) in enumerate(window_slices(edges)):
            if hi == lo:          # empty per-file window -> NaN
                assert np.isnan(res["ltsa"][i]).all()
                assert np.isnan(res["min_welch"][i]).all()
                continue
            assert np.allclose(res["ltsa"][i], w64[lo:hi].mean(0),
                               rtol=1e-6), i
            assert np.array_equal(res["min_welch"][i],
                                  res["welch"][lo:hi].min(0)), i
            assert np.array_equal(res["max_welch"][i],
                                  res["welch"][lo:hi].max(0)), i
        assert np.allclose(res["spd"],
                           spd_oracle(frame_db_oracle(m, P), edges),
                           atol=1e-7)

        # ---- mid-window resume is bitwise-identical ----
        n_steps = res.plan.n_steps
        limit = min(resume_steps, max(n_steps - 1, 0))
        if limit > 0:
            with tempfile.TemporaryDirectory() as d:
                build(sink=d, limit=limit).run()
                resumed = build(sink=d).run()
                for name in WINDOWED:     # empty windows are NaN
                    assert np.array_equal(resumed.windows[name],
                                          res.windows[name],
                                          equal_nan=True), name
                assert np.array_equal(
                    np.asarray(resumed["welch"]), res["welch"])
                assert np.array_equal(resumed["mean_welch"],
                                      res["mean_welch"])


def spd_counts_segment_sum(db, mask):
    """The SPD update as a scatter: one flat segment-sum per record over
    ``freq * SPD_N_DB + dbin`` ids, invalid frames to a dropped extra
    segment — the oracle for the dense count."""
    n_bins = db.shape[-1]
    freq = jnp.broadcast_to(jnp.arange(n_bins), db.shape)
    dbin = jnp.floor((db - api.SPD_DB_MIN) / api.SPD_DB_STEP).astype(
        jnp.int32)
    valid = ((db >= api.SPD_DB_MIN) & (db < api.SPD_DB_MAX)
             & mask[:, None, None])
    flat_ids = jnp.where(valid, freq * api.SPD_N_DB + dbin,
                         n_bins * api.SPD_N_DB)

    def one_record(ids):
        h = jax.ops.segment_sum(
            jnp.ones(ids.size, jnp.int32), ids.reshape(-1),
            num_segments=n_bins * api.SPD_N_DB + 1)
        return h[:-1].reshape(n_bins, api.SPD_N_DB)

    return jax.vmap(one_record)(flat_ids)


def _below(x):
    return np.nextafter(np.float32(x), np.float32(-np.inf))


SPD_EDGES = np.float32(api.SPD_DB_MIN) + np.float32(api.SPD_DB_STEP) \
    * np.arange(api.SPD_N_DB + 1, dtype=np.float32)
# dB values the binning decides at a boundary
SPD_VALUES = {
    "bin_edges": np.concatenate([SPD_EDGES, _below(SPD_EDGES),
                                 np.nextafter(SPD_EDGES, np.float32(np.inf))]),
    # _below(60) and the next float down round to bin SPD_N_DB in float32
    "range_ends": np.float32([api.SPD_DB_MIN, api.SPD_DB_MAX,
                              _below(api.SPD_DB_MAX),
                              _below(_below(api.SPD_DB_MAX)),
                              _below(api.SPD_DB_MIN), -1e4, 1e4]),
    "nonfinite": np.float32([np.nan, np.inf, -np.inf, -75.0, 0.0]),
}


class TestSpdDenseCount:
    """The dense SPD count against the segment-sum formulation, bitwise,
    over paper-like shapes, odd frame counts and the dB values the
    binning decides at a boundary."""

    @pytest.mark.parametrize("shape", [(2, 5, 3), (3, 1537, 129),
                                       (2, 80, 2049), (3, 997, 7)],
                             ids=["tiny", "set1_like", "set2_like",
                                  "prime_frames"])
    @pytest.mark.parametrize("values", [None, *SPD_VALUES],
                             ids=["noise", *SPD_VALUES])
    @pytest.mark.parametrize("masked", [(), (1,)], ids=["all", "masked"])
    def test_bitwise_equal_to_segment_sum(self, shape, values, masked):
        rng = np.random.default_rng(7)
        db = rng.normal(-75.0, 20.0, shape).astype(np.float32)
        if values is not None:
            hit = rng.random(shape) < 0.5
            db[hit] = rng.choice(SPD_VALUES[values], hit.sum())
        mask = np.ones(shape[0], bool)
        mask[list(masked)] = False
        want = jax.jit(spd_counts_segment_sum)(db, mask)
        got = jax.jit(features._spd_update)(db, mask)["counts"]
        assert got.dtype == want.dtype == jnp.int32
        assert np.array_equal(got, want)
        assert int(want.sum()) > 0


class TestCustomReduction:
    def test_registry_free_inline_reduction(self):
        """A user reduction (windowed energy sum) with no engine edits."""
        spec = api.FeatureSpec(
            name="energy", shape=None,
            compute=lambda ctx: jnp.sum(ctx.records ** 2, axis=-1,
                                        keepdims=True),
            reductions=(api.Reduction(
                out_name="window_energy",
                init=lambda m, p: (api.StateField("sum", (1,)),),
                update=lambda v, mask: {
                    "sum": v * mask[:, None].astype(v.dtype)},
                finalize=lambda st: st["sum"],
                out_shape=lambda m, p: (1,)),))
        res = (api.job(M, P).features("welch", spec).window(records=4)
               .chunk(4).run())
        assert res["window_energy"].shape == (3, 1)
        assert (res["window_energy"] > 0).all()
