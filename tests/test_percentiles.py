"""The percentiles' order statistics: the selection returns the elements
the sort puts at the taps, and a job's committed percentiles are the
same bits on either side of the select/sort crossover."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import api
from repro.api import engine, features
from repro.core.manifest import DatasetManifest
from repro.core.params import DepamParams
from repro.kernels import select


def field(shape, seed):
    """dB-like levels on a quarter-dB grid (many exact ties), the first
    column of record 0 one constant value."""
    rng = np.random.default_rng(seed)
    db = np.round(rng.normal(60.0, 12.0, shape) * 4) / 4
    db[0, :, 0] = 42.5
    return db.astype(np.float32)


def nan_and_zeros(shape, seed):
    """Frames holding NaN in one column, -0.0 and +0.0 in another."""
    db = field(shape, seed)
    db[0, 3, 1] = np.nan
    db[-1, :, 2] = np.where(np.arange(shape[1]) % 3, -0.0, 0.0)
    db[-1, : shape[1] // 2, 3] = 0.0
    return db


CASES = {
    "set1_ties": lambda: field((2, 15359, 129), 1),
    "nan_and_zeros": lambda: nan_and_zeros((3, 501, 9), 2),
    "set2": lambda: field((4, 80, 2049), 3),
    "frames_1": lambda: field((2, 1, 5), 4),
    "frames_2": lambda: field((2, 2, 5), 5),
    "frames_3": lambda: field((2, 3, 5), 6),
}


def sorted_taps(db, lo, hi):
    srt = np.sort(db, axis=-2)
    return srt[:, lo], srt[:, hi]


def levels(db, min_frames, monkeypatch):
    """The percentiles feature's levels of ``db`` with the crossover
    set to ``min_frames``."""
    monkeypatch.setattr(features, "PCT_SELECT_MIN_FRAMES", min_frames)

    def compute(psd):
        ctx = type("Ctx", (), {})()
        ctx.params, ctx.frame_psd = DepamParams(gain_db=0.0), psd
        ctx.use_kernels = True
        return features._percentiles_compute(ctx)
    return np.asarray(jax.jit(compute)(10.0 ** (jnp.asarray(db) / 10.0)))


def same_bits(a, b):
    """Bitwise equal, except that +0.0 and -0.0 count as one value:
    the sort keeps tied zeros in frame order, the selection returns
    +0.0 (a dB level is never -0.0)."""
    return bool(((a.view(np.int32) == b.view(np.int32))
                 | ((a == 0) & (b == 0))).all())


@pytest.mark.parametrize("case", CASES)
def test_selection_equals_sort(case):
    db = CASES[case]()
    lo, hi, _, _ = features._percentile_taps(db.shape[1])
    got = select.select_order_stats(db, tuple(lo.tolist()),
                                    tuple(hi.tolist()))
    want = sorted_taps(db, lo, hi)
    clean = ~np.isnan(db).any(axis=1)[:, None, :]     # (batch, 1, n_bins)
    for g, w in zip(got, want):
        g = np.asarray(g)
        assert np.array_equal(np.where(clean, g, 0), np.where(clean, w, 0))
        assert same_bits(np.where(clean, g, 0), np.where(clean, w, 0))


@pytest.mark.parametrize("case", CASES)
def test_both_sides_of_the_crossover_agree(case, monkeypatch):
    db = CASES[case]()
    selected = levels(db, 0, monkeypatch)
    sorted_ = levels(db, db.shape[1] + 1, monkeypatch)
    assert same_bits(selected, sorted_)
    assert np.array_equal(np.isnan(selected), np.isnan(db).any(axis=1)
                          [:, None, :].repeat(selected.shape[1], 1))


def test_paper_sets_fall_either_side_of_the_crossover():
    from repro.core.params import PARAM_SET_1, PARAM_SET_2
    assert features.selects_order_stats(PARAM_SET_1.frames_per_record)
    assert not features.selects_order_stats(PARAM_SET_2.frames_per_record)


P = DepamParams(nfft=256, window_size=256, window_overlap=128,
                record_size_sec=0.25)
M = DatasetManifest(n_files=2, records_per_file=4, record_size=P.record_size,
                    fs=P.fs, seed=5)


def test_committed_percentiles_equal_the_sort_path(tmp_path, monkeypatch):
    """One job's committed percentiles memmap, selected and sorted."""
    out = {}
    for path, min_frames in (("select", 0), ("sort", 1 << 30)):
        monkeypatch.setattr(features, "PCT_SELECT_MIN_FRAMES", min_frames)
        jax.clear_caches()
        engine.compile_step.cache_clear()
        d = str(tmp_path / path)
        (api.job(M, P).features("welch", "percentiles").chunk(4).to(d)
         .run())
        out[path] = np.load(f"{d}/percentiles.npy", mmap_mode="r")
    jax.clear_caches()
    engine.compile_step.cache_clear()
    assert out["select"].shape == (M.n_records, 7, P.n_bins)
    assert np.array_equal(out["select"].view(np.int32),
                          out["sort"].view(np.int32))
