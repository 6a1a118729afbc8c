"""How ``correct`` is decided: what a job of the window committed,
against the float64 reference of the same corpus.

The numbers compared, each a widest gap over what is checked:

  * ``welch_rel``, ``spl_db``, ``tol_db``: the per-record features of a
    sample of records drawn from the seed (every file, the first and the
    last record among them), read back from the store;
  * ``pct_db``: the spectrum percentiles of those records;
  * ``ltsa_rel``, ``minmax_rel``: every file's windowed panels, from the
    store; ``mean_welch_rel``: the epoch mean the job returned;
  * ``spd_moved``: the most frames, over the frequency bins of one file
    drawn from the seed, that the job's SPD puts in another dB bin than
    the reference does (half the L1 distance of the two histograms);
  * ``event_mismatch``: records of that file and of the sample whose
    event log differs from the reference's in count, onset, duration or
    peak bin (an exact comparison); ``peak_db`` and the impulsive
    metrics ``sel_db``, ``peak_level_db``, ``kurtosis_rel`` are gaps
    over the matched events, ``rise_mismatch`` counts rise times off by
    a sample or more (exact).

Records on which rounding may decide the event log either way (a
reference frame within ``EVENT_MARGIN_DB`` of the open or close level)
are left out of the event comparison and counted.
"""
from __future__ import annotations

import concurrent.futures as cf
import dataclasses

import numpy as np

from . import corpus, reference as ref

EVENT_MARGIN_DB = 1e-3
N_SAMPLED = 8


@dataclasses.dataclass
class Outputs:
    """What a job produced, as the comparison reads it.  Arrays are
    indexed by global record id (per-record), by file (windows)."""

    welch: np.ndarray
    spl: np.ndarray
    tol: np.ndarray
    ltsa: np.ndarray
    mean_welch: np.ndarray
    percentiles: np.ndarray | None = None
    spd: np.ndarray | None = None
    min_welch: np.ndarray | None = None
    max_welch: np.ndarray | None = None
    events: object = None          # .counts and .record(i) -> rows
    impulsive: object = None

    @classmethod
    def of_job(cls, store: str, result) -> "Outputs":
        """A completed job: the features and windows read back from its
        committed store, the epoch mean and event logs it returned (the
        latter read back from the store by the sink)."""
        def load(name):
            try:
                return np.load(f"{store}/{name}.npy", mmap_mode="r")
            except FileNotFoundError:
                return None
        ev = result.events or {}
        return cls(welch=load("welch"), spl=load("spl"), tol=load("tol"),
                   ltsa=load("ltsa"), mean_welch=result.epoch["mean_welch"],
                   percentiles=load("percentiles"), spd=load("spd"),
                   min_welch=load("min_welch"), max_welch=load("max_welch"),
                   events=ev.get("events"), impulsive=ev.get("impulsive"))


def sample(seed: int, n_files: int, per_file: int) -> tuple[int, list[int]]:
    """The file whose frame-level products are checked, and the sampled
    records, drawn from the seed."""
    rng = np.random.default_rng(corpus.seed_words(seed) + [7])
    f = int(rng.integers(n_files))
    n = n_files * per_file
    recs = {0, n - 1} | {int(rng.integers(i * per_file, (i + 1) * per_file))
                         for i in range(n_files)}
    while len(recs) < N_SAMPLED + 2:
        recs.add(int(rng.integers(n)))
    return f, sorted(recs)


class Reference:
    """The reference outputs of one corpus: the Welch PSD of every
    record, and the frame-level products of the checked records."""

    def __init__(self, corpus_dir: str, config: dict, mix: dict,
                 seed: int):
        p = self.p = ref.Params.of(config)
        self.config, self.mix = config, mix
        self.n_files = config["n_files"]
        file_len = int(round(config["file_sec"] * config["fs"]))
        self.per_file = file_len // p.record_size
        self.file, self.records = sample(seed, self.n_files, self.per_file)
        frame_level = "percentiles" in mix["features"] or mix["events"]
        want = set(self.records)
        if frame_level:
            want |= set(range(self.file * self.per_file,
                              (self.file + 1) * self.per_file))
        n = self.n_files * self.per_file
        self.welch = np.zeros((n, p.n_bins))
        self.x: dict[int, np.ndarray] = {}
        self.fpsd: dict[int, np.ndarray] = {}

        def one(i, pcm):
            r = i % self.per_file
            x = ref.decode(pcm[r * p.record_size:(r + 1) * p.record_size])
            fp = ref.frame_psd(x, p)
            self.welch[i] = fp.mean(axis=0)
            if frame_level and i in want:
                self.x[i], self.fpsd[i] = x, fp

        with cf.ThreadPoolExecutor(ref.WORKERS) as pool:
            for fi in range(self.n_files):
                pcm = corpus.read_file(corpus_dir, fi)
                list(pool.map(lambda i: one(i, pcm),
                              range(fi * self.per_file,
                                    (fi + 1) * self.per_file)))

    def file_records(self, fi: int) -> slice:
        return slice(fi * self.per_file, (fi + 1) * self.per_file)


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    gap = np.abs(a - b) / np.abs(b)
    return float(np.max(np.where(np.isfinite(gap), gap, np.inf)))


def _gap(a, b) -> float:
    gap = np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
    return float(np.max(np.where(np.isfinite(gap), gap, np.inf)))


def readings(out: Outputs, r: Reference) -> tuple[dict, dict]:
    """Every compared number of ``out`` against ``r``, and facts about
    the comparison (counts) that are printed but not compared."""
    p = r.p
    recs = r.records
    got: dict[str, float] = {}
    info: dict[str, float] = {"records_sampled": len(recs)}
    w_ref = r.welch[recs]
    got["welch_rel"] = _rel(np.asarray(out.welch)[recs], w_ref)
    got["spl_db"] = _gap(np.asarray(out.spl)[recs], ref.spl(w_ref, p))
    got["tol_db"] = _gap(np.asarray(out.tol)[recs], ref.tol(w_ref, p))
    files = range(r.n_files)
    ltsa = np.stack([r.welch[r.file_records(f)].mean(axis=0) for f in files])
    got["ltsa_rel"] = _rel(out.ltsa, ltsa)
    got["mean_welch_rel"] = _rel(out.mean_welch, r.welch.mean(axis=0))
    if out.min_welch is not None:
        lo = np.stack([r.welch[r.file_records(f)].min(axis=0) for f in files])
        hi = np.stack([r.welch[r.file_records(f)].max(axis=0) for f in files])
        got["minmax_rel"] = max(_rel(out.min_welch, lo),
                                _rel(out.max_welch, hi))
    if out.percentiles is not None:
        got["pct_db"] = max(_gap(np.asarray(out.percentiles)[i],
                                 ref.percentiles(r.fpsd[i])) for i in recs)
    if out.spd is not None:
        rows = range(r.file * r.per_file, (r.file + 1) * r.per_file)
        counts = sum(ref.spd_counts(r.fpsd[i]) for i in rows)
        share = np.abs(np.asarray(out.spd[r.file], np.float64)
                       - ref.spd_density(counts)) * ref.SPD_DB_STEP
        got["spd_moved"] = float(np.max(share.sum(axis=-1)
                                        * counts.sum(axis=-1)) / 2.0)
    if out.events is not None:
        got.update(_event_readings(out, r, info))
    return got, info


def _event_readings(out: Outputs, r: Reference, info: dict) -> dict:
    p, cfg = r.p, r.config
    thr, hyst = cfg["event_threshold_db"], cfg["event_hysteresis_db"]
    checked = sorted(r.fpsd)
    mismatch = undecided = matched = rise = 0
    gaps = {"peak_db": 0.0, "sel_db": 0.0, "peak_level_db": 0.0,
            "kurtosis_rel": 0.0}
    for i in checked:
        fp = r.fpsd[i]
        frame_db = ref.db(fp.sum(axis=-1) * p.df)
        if ref.undecided(frame_db, thr, hyst, EVENT_MARGIN_DB):
            undecided += 1
            continue
        want = ref.detect(frame_db, fp, thr, hyst, EVENT_MARGIN_DB)
        count = int(out.events.counts[i])
        rows, vals = out.events.record(i), out.impulsive.record(i)
        same = count == len(want) and len(rows) == len(want) and all(
            (int(row[0]), int(row[1])) == (e.onset, e.duration)
            and int(row[2]) in e.peak_bins for row, e in zip(rows, want))
        if not same:
            mismatch += 1
            continue
        for row, val, e in zip(rows, vals, want):
            matched += 1
            imp = ref.impulsive(r.x[i], e.onset, e.duration, p)
            gaps["peak_db"] = max(gaps["peak_db"], abs(row[3] - e.peak_db))
            gaps["sel_db"] = max(gaps["sel_db"], abs(val[0] - imp[0]))
            gaps["peak_level_db"] = max(gaps["peak_level_db"],
                                        abs(val[1] - imp[1]))
            gaps["kurtosis_rel"] = max(gaps["kurtosis_rel"], abs(
                val[2] - imp[2]) / (1.0 + abs(imp[2])))
            rise += int(round(abs(val[3] - imp[3]) * p.fs) != 0)
    info.update(event_records=len(checked), event_records_undecided=undecided,
                events_matched=matched)
    out_ = {"event_mismatch": float(mismatch), "rise_mismatch": float(rise)}
    out_.update({k: float(v) for k, v in gaps.items()})
    if matched == 0:
        # nothing matched: the event gaps have nothing to say
        out_["event_mismatch"] = max(out_["event_mismatch"], 1.0)
    return out_


def verdict(got: dict, limits: dict) -> tuple[bool, list[str]]:
    """Each number against its limit; a number without a limit, or a
    limit without a number, is a failure."""
    lines, ok = [], True
    for name in sorted(set(got) | set(limits)):
        value, limit = got.get(name), limits.get(name)
        good = value is not None and limit is not None and value <= limit
        ok &= good
        lines.append(f"{name} {value!r} limit {limit!r}"
                     f"{'' if good else ' FAIL'}")
    return ok, lines
