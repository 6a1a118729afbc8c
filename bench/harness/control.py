"""The control of the comparison: the reference put in the program's
place and computed one precision below what the configuration states.

The configuration states float32 data with the DFT and TOL matmuls at
``Precision.HIGHEST``.  The control runs those matmuls at ``HIGH``
(three bfloat16 passes) and everything else, float32 in the program,
in bfloat16: the power, its scaling and averaging, every level in dB,
the sort behind the percentiles, the SPD bins, the detector's SPL and
the impulsive sums.  It runs on whatever device JAX has (the chip, in a
calibration run), and :func:`check.readings` compares it with the
float64 reference exactly as it compares a job; it has to come out as
not correct.
"""
from __future__ import annotations

import numpy as np

from . import corpus, reference as ref
from .check import Outputs, Reference


class _Log:
    """An event log as the comparison reads it."""

    def __init__(self, n: int):
        self.counts = np.zeros(n, np.int64)
        self.rows: dict[int, np.ndarray] = {}

    def record(self, i: int) -> np.ndarray:
        return self.rows.get(i, np.zeros((0, 4)))


def _dft(p: ref.Params):
    """Window-folded real-DFT matrices (window_size, n_bins), float32."""
    j = np.arange(p.window_size)[:, None]
    k = np.arange(p.n_bins)[None, :]
    ang = 2 * np.pi * j * k / p.nfft
    w = p.taper()[:, None]
    return ((w * np.cos(ang)).astype(np.float32),
            (-w * np.sin(ang)).astype(np.float32))


def control_outputs(corpus_dir: str, r: Reference) -> Outputs:
    import jax
    import jax.numpy as jnp

    p, cfg, mix = r.p, r.config, r.mix
    bf = jnp.bfloat16
    high = jax.lax.Precision.HIGH
    c, s = (jnp.asarray(a) for a in _dft(p))
    dens = jnp.asarray(p.density(), bf)
    bands = jnp.asarray(ref.band_matrix(p), jnp.float32)

    @jax.jit
    def fpsd(frames):
        re = jnp.dot(frames, c, precision=high)
        im = jnp.dot(frames, s, precision=high)
        return (re * re + im * im).astype(bf) * dens

    def db(x):
        x = jnp.asarray(x, bf)
        return (jnp.log10(jnp.maximum(x, bf(1e-30))) * bf(10.0)).astype(bf)

    def frames_of(i, pcm):
        lo = (i % r.per_file) * p.record_size
        x = (pcm[lo:lo + p.record_size].astype(np.float32)
             * np.float32(ref.PCM_SCALE))
        return x, np.lib.stride_tricks.sliding_window_view(
            x, p.window_size)[::p.hop][:p.frames]

    n = r.n_files * r.per_file
    welch = np.zeros((n, p.n_bins), np.float32)
    kept: dict[int, tuple] = {}
    for fi in range(r.n_files):
        pcm = corpus.read_file(corpus_dir, fi)
        for i in range(fi * r.per_file, (fi + 1) * r.per_file):
            x, fr = frames_of(i, pcm)
            fp = fpsd(jnp.asarray(fr))
            welch[i] = np.asarray(jnp.mean(fp, axis=0).astype(bf),
                                  np.float32)
            if i in r.fpsd:
                kept[i] = (x, fp)
    wj = jnp.asarray(welch, bf)
    spl = np.asarray(db(jnp.sum(wj, axis=-1) * bf(p.df)), np.float32)
    tol = np.asarray(db(jnp.dot(wj.astype(jnp.float32), bands,
                                precision=high).astype(bf) * bf(p.df)),
                     np.float32)
    per = [slice(f * r.per_file, (f + 1) * r.per_file)
           for f in range(r.n_files)]
    out = Outputs(
        welch=welch, spl=spl, tol=tol,
        ltsa=np.stack([np.asarray(jnp.mean(wj[sl], axis=0).astype(bf),
                                  np.float32) for sl in per]),
        mean_welch=np.asarray(jnp.mean(wj, axis=0).astype(bf), np.float32))
    if "minmax" in mix["features"]:
        out.min_welch = np.stack([welch[sl].min(axis=0) for sl in per])
        out.max_welch = np.stack([welch[sl].max(axis=0) for sl in per])
    if "percentiles" in mix["features"]:
        out.percentiles = np.zeros((n, len(ref.SPECTRUM_PERCENTILES),
                                    p.n_bins), np.float32)
        q = jnp.asarray(ref.SPECTRUM_PERCENTILES, jnp.float32)
        for i in r.records:
            srt = jnp.sort(db(kept[i][1]), axis=0)
            out.percentiles[i] = np.asarray(
                jnp.percentile(srt, q, axis=0).astype(bf), np.float32)
    if "spd" in mix["features"]:
        counts = 0
        for i in range(r.file * r.per_file, (r.file + 1) * r.per_file):
            counts = counts + ref.spd_counts(
                10.0 ** (np.asarray(db(kept[i][1]), np.float64) / 10.0))
        out.spd = np.zeros((r.n_files,) + counts.shape, np.float32)
        out.spd[r.file] = ref.spd_density(counts)
    if mix["events"]:
        out.events, out.impulsive = _events(kept, r, db)
    return out


def _events(kept: dict, r: Reference, db):
    """The detector and the impulsive sums over bfloat16 levels and
    samples."""
    import jax.numpy as jnp

    p, cfg = r.p, r.config
    bf = jnp.bfloat16
    n = r.n_files * r.per_file
    events, imp = _Log(n), _Log(n)
    for i, (x, fp) in kept.items():
        frame_db = np.asarray(db(jnp.sum(fp, axis=-1) * bf(p.df)),
                              np.float64)
        found = ref.detect(frame_db, np.asarray(fp, np.float64),
                           cfg["event_threshold_db"],
                           cfg["event_hysteresis_db"], 0.0)
        events.counts[i] = imp.counts[i] = len(found)
        xb = jnp.asarray(x, bf)
        rows, vals = [], []
        for e in found:
            s0 = e.onset * p.hop
            s1 = min((e.onset + e.duration - 1) * p.hop + p.window_size,
                     x.size)
            seg = xb[s0:s1]
            e2 = seg * seg
            mean = jnp.mean(seg).astype(bf)
            cseg = (seg - mean).astype(bf)
            m2 = jnp.mean(cseg * cseg).astype(bf)
            m4 = jnp.mean((cseg * cseg) * (cseg * cseg)).astype(bf)
            vals.append([float(db(jnp.sum(e2).astype(bf) / bf(p.fs))),
                         float(db(jnp.max(e2))),
                         float((m4 / (m2 * m2)).astype(bf)),
                         float(jnp.argmax(e2)) / p.fs])
            rows.append([e.onset, e.duration, min(e.peak_bins), e.peak_db])
        events.rows[i] = np.asarray(rows, np.float64).reshape(-1, 4)
        imp.rows[i] = np.asarray(vals, np.float64).reshape(-1, 4)
    return events, imp
