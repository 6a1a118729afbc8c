"""The plain float64 reference of every DEPAM output, in NumPy/SciPy.

It follows the documented semantics of each output (the program's
docstrings and the paper's Table 2.1 chain) and imports nothing of the
program: Welch PSD with scipy's 'density' scaling and a periodic
window, wideband SPL, IEC 61260 base-10 third-octave levels, spectrum
percentiles of the frame spectrogram in dB, the spectral probability
density, per-file LTSA and min/max panels, the epoch mean, the
Schmitt-trigger event detector and the impulsive metrics of each event.
The Welch, SPL, TOL, event and impulsive parts start from
``chip_smoke.py``'s reference.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import scipy.fft

PCM_SCALE = 1.0 / 32767.0
SPECTRUM_PERCENTILES = (1.0, 5.0, 25.0, 50.0, 75.0, 95.0, 99.0)
SPD_DB_MIN, SPD_DB_MAX, SPD_DB_STEP = -120.0, 60.0, 3.0
SPD_N_DB = int(round((SPD_DB_MAX - SPD_DB_MIN) / SPD_DB_STEP))
TOL_FMIN = 10.0
WORKERS = 8


@dataclasses.dataclass(frozen=True)
class Params:
    """The analysis parameters of a configuration file."""

    fs: float
    nfft: int
    window_size: int
    hop: int
    record_size: int
    window: str

    @classmethod
    def of(cls, config: dict) -> "Params":
        return cls(fs=float(config["fs"]), nfft=config["nfft"],
                   window_size=config["window_size"],
                   hop=config["window_size"] - config["window_overlap"],
                   record_size=int(round(config["record_size_sec"]
                                         * config["fs"])),
                   window=config["window"])

    @property
    def n_bins(self) -> int:
        return self.nfft // 2 + 1

    @property
    def df(self) -> float:
        return self.fs / self.nfft

    @property
    def frames(self) -> int:
        return (self.record_size - self.window_size) // self.hop + 1

    def taper(self) -> np.ndarray:
        """The periodic analysis window (scipy ``get_window``)."""
        n = np.arange(self.window_size)
        if self.window == "hamming":
            return 0.54 - 0.46 * np.cos(2 * np.pi * n / self.window_size)
        if self.window == "hann":
            return 0.5 - 0.5 * np.cos(2 * np.pi * n / self.window_size)
        return np.ones(self.window_size)

    def density(self) -> np.ndarray:
        """One-sided density scale per bin: 2/(fs sum w^2), DC and
        Nyquist once."""
        w = self.taper()
        s = np.full(self.n_bins, 2.0 / (self.fs * np.sum(w * w)))
        s[0] /= 2.0
        if self.nfft % 2 == 0:
            s[-1] /= 2.0
        return s


def decode(pcm: np.ndarray) -> np.ndarray:
    return np.asarray(pcm, np.float64) * PCM_SCALE


def frame_psd(x: np.ndarray, p: Params) -> np.ndarray:
    """(record_size,) float64 -> (frames, n_bins) one-sided PSD."""
    frames = np.lib.stride_tricks.sliding_window_view(
        x, p.window_size)[::p.hop][:p.frames]
    spec = scipy.fft.rfft(frames * p.taper(), n=p.nfft, axis=-1)
    return (spec.real ** 2 + spec.imag ** 2) * p.density()


def db(power: np.ndarray) -> np.ndarray:
    return 10.0 * np.log10(np.maximum(power, 1e-30))


def band_matrix(p: Params) -> np.ndarray:
    """(n_bins, n_bands) fractional membership of each PSD bin's
    [f - df/2, f + df/2) support (DC: [0, df/2)) in the IEC 61260
    base-10 third-octave bands whose centre lies in [10 Hz, fs/2)."""
    g = 10.0 ** 0.3
    n_lo = int(np.ceil(3.0 * np.log(TOL_FMIN / 1000.0) / np.log(g)))
    n_hi = int(np.floor(3.0 * np.log(p.fs / 2.0 / 1000.0) / np.log(g)))
    fc = 1000.0 * g ** (np.arange(n_lo, n_hi + 1) / 3.0)
    lo, hi = fc * g ** (-1.0 / 6.0), fc * g ** (1.0 / 6.0)
    f = np.arange(p.n_bins) * p.df
    bin_lo = np.maximum(f - p.df / 2.0, 0.0)
    bin_hi = f + p.df / 2.0
    overlap = (np.minimum(bin_hi[:, None], hi[None, :])
               - np.maximum(bin_lo[:, None], lo[None, :]))
    return np.clip(overlap, 0.0, None) / (bin_hi - bin_lo)[:, None]


def spl(welch: np.ndarray, p: Params) -> np.ndarray:
    return db(welch.sum(axis=-1) * p.df)


def tol(welch: np.ndarray, p: Params) -> np.ndarray:
    return db(welch @ band_matrix(p) * p.df)


def percentiles(fpsd: np.ndarray) -> np.ndarray:
    """(frames, n_bins) -> (7, n_bins) dB, linear interpolation."""
    return np.percentile(db(fpsd), SPECTRUM_PERCENTILES, axis=0)


def spd_counts(fpsd: np.ndarray) -> np.ndarray:
    """(frames, n_bins) -> (n_bins, SPD_N_DB) frame counts of each dB
    bin in [SPD_DB_MIN, SPD_DB_MAX), per frequency bin."""
    level = db(fpsd)
    n_bins = level.shape[1]
    dbin = np.floor((level - SPD_DB_MIN) / SPD_DB_STEP).astype(np.int64)
    ok = (level >= SPD_DB_MIN) & (level < SPD_DB_MAX)
    ids = (np.arange(n_bins)[None, :] * SPD_N_DB + dbin)[ok]
    return np.bincount(ids, minlength=n_bins * SPD_N_DB).reshape(
        n_bins, SPD_N_DB)


def spd_density(counts: np.ndarray) -> np.ndarray:
    """Counts -> probability density over dB per frequency bin."""
    total = counts.sum(axis=-1, keepdims=True)
    return counts / np.where(total > 0, total * SPD_DB_STEP, 1.0)


@dataclasses.dataclass
class Event:
    onset: int
    duration: int
    peak_db: float
    peak_bins: frozenset      # every bin the peak could round to


def detect(frame_db: np.ndarray, fpsd: np.ndarray, thr: float, hyst: float,
           margin_db: float) -> list[Event]:
    """The Schmitt trigger over per-frame SPL: a frame at or above
    ``thr`` opens an event, the first frame below ``thr - hyst`` closes
    it (the duration excludes it), an event open at the record end
    closes there.  The peak is the loudest frame (the first of equals);
    ``peak_bins`` holds the argmax bin of every frame within
    ``margin_db`` of the peak and every bin of such a frame within
    ``margin_db`` of its maximum, the bins a rounding can report."""
    lo = thr - hyst
    above = np.flatnonzero(frame_db >= thr)
    below = np.flatnonzero(frame_db < lo)
    n = frame_db.size
    events, f = [], 0
    while True:
        k = np.searchsorted(above, f)
        if k == above.size:
            return events
        start = int(above[k])
        j = np.searchsorted(below, start)
        end = int(below[j]) if j < below.size else n
        span = frame_db[start:end]
        peak = float(span.max())
        bins = set()
        for fr in np.flatnonzero(span >= peak - margin_db) + start:
            row = db(fpsd[fr])
            bins.update(np.flatnonzero(row >= row.max() - margin_db).tolist())
        events.append(Event(start, end - start, peak, frozenset(bins)))
        f = end


def undecided(frame_db: np.ndarray, thr: float, hyst: float,
              margin_db: float) -> bool:
    """Whether a frame lies within ``margin_db`` of the open or close
    level, where float32 rounding may decide the event log either way."""
    return bool(np.any(np.abs(frame_db - thr) <= margin_db)
                or np.any(np.abs(frame_db - (thr - hyst)) <= margin_db))


def impulsive(x: np.ndarray, onset: int, dur: int, p: Params
              ) -> np.ndarray:
    """SEL (dB re 1 uPa^2 s), zero-to-peak level (dB), kurtosis and the
    rise time (s) of one event's samples [onset*hop, (onset+dur-1)*hop
    + window_size)."""
    s0 = onset * p.hop
    seg = x[s0:min((onset + dur - 1) * p.hop + p.window_size, len(x))]
    e = seg * seg
    c = seg - seg.mean()
    m2, m4 = np.mean(c ** 2), np.mean(c ** 4)
    return np.array([db(e.sum() / p.fs), db(e.max()),
                     m4 / max(m2 * m2, 1e-30), float(np.argmax(e)) / p.fs])
