#!/usr/bin/env python3
"""Smoke run of the DEPAM job on a TPU — the quickest proof that the
system starts on the chip and computes the right numbers there.

    python chip_smoke.py [--seed N]                # one chip
    python chip_smoke.py --four-chips [--seed N]   # the data-parallel path

This is a smoke run, not a benchmark: its times include compilation,
file IO and the host-side reference checks, and no metric is defined
on them.  Everything runs in this one process, which holds the chip.

One chip (the default) drives the real entry points at the paper's
widths over two generated 45-minute wav files (fs 32,768 Hz, 16-bit
mono), with the int16 transport and every feature:

  * the batch CLI (``depam_run.main``) for parameter set 1 (90 records
    of 60 s) and set 2 (540 records of 10 s), checked on the host
    against SciPy/NumPy float64 references for the first, middle and
    last record: Welch, SPL, TOL, the event detector and the impulsive
    metrics;
  * the bitwise-or-loud invariants on the chip: the float32 and int16
    transports give the same bytes on disk, and a set-1 job stopped
    half way and resumed gives the same bytes as the uninterrupted run;
  * the multi-tenant service (``serve.run``) at set 1's 60 s records,
    with its concurrent-vs-solo bitwise verification.

``--four-chips`` runs only the set-1 job at ``.shards(4)`` on a
four-chip mesh and on one chip, asserts that every output namespace is
bitwise-equal, and that the step really is split: the payload and every
per-record output have one shard on each of four devices, and the
compiled step holds no collective.

It fails — never falls back — when JAX finds no TPU, when the Pallas
kernels would run in interpret mode, or when a compiled step lacks one
``tpu_custom_call`` for each kernel on its path.  The last line of
stdout is ``{"ok": true, "device": {...}}``; nothing is printed there
on failure.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import filecmp
import json
import os
import pathlib
import sys
import tempfile
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent

FILE_SEC = 45 * 60                    # the paper's wav file length
N_FILES = 2
FEATURES = ("welch", "spl", "tol", "percentiles", "ltsa", "spd", "minmax")
# records per step, from the v5e compile rehearsal's memory analysis:
# set 1 at 8 records takes ~0.45 GB of temporaries (the impulsive span
# masks over the waveform dominate), set 2 at 32 records ~0.09 GB
CHUNK = {1: 8, 2: 32}
# the generated signal: a noise floor with decaying pings
NOISE_RMS = 0.02
PING_AMPLITUDE = (0.3, 0.7)
PING_GAP_SEC = (4.0, 10.0)
PING_LEN = 2048
# first event threshold tried and hysteresis, per set (dB re 1 uPa);
# the threshold then steps up until no reference frame lies within
# EVENT_MARGIN_DB of the open or close level
EVENT_LEVELS = {1: (-24.0, 3.0), 2: (-31.0, 1.5)}
EVENT_MARGIN_DB = 1e-3
# the repo's tolerances: Welch vs scipy (tests/test_system.py), and the
# impulsive metrics vs float64 (tests/test_events.py)
WELCH_RTOL = 5e-3
LEVEL_TOL_DB = 10.0 * np.log10(1.0 + WELCH_RTOL)
PEAK_DB_TOL = 1e-3
IMPULSIVE_DB_TOL = 1e-3
KURTOSIS_TOL = 1e-3
KERNELS = {1: {"welch_psd", "frame_psd", "tol_levels", "detect_events"},
           2: {"ct_frame_psd", "welch_mean", "tol_levels",
               "detect_events"}}


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


# -- the generated dataset ------------------------------------------------

def write_data(root: str, seed: int) -> None:
    """Two FILE_SEC-long 16-bit wav files from ``seed``: a Gaussian
    noise floor plus decaying sinusoid pings at random gaps."""
    from repro.core.manifest import DatasetManifest
    from repro.core.params import PAPER_FS
    from repro.data.wavio import write_dataset

    fs = int(PAPER_FS)
    t = np.arange(PING_LEN)
    ping = (np.exp(-t / 400.0) * np.sin(2 * np.pi * 0.05 * t)) \
        .astype(np.float32)

    def gen(fi, n):
        rng = np.random.default_rng([seed, fi])
        x = rng.standard_normal(n, dtype=np.float32) * np.float32(NOISE_RMS)
        pos = int(rng.uniform(*PING_GAP_SEC) * fs)
        while pos + PING_LEN < n:
            x[pos:pos + PING_LEN] += np.float32(
                rng.uniform(*PING_AMPLITUDE)) * ping
            pos += int(rng.uniform(*PING_GAP_SEC) * fs)
        return x

    m = DatasetManifest(n_files=N_FILES, records_per_file=FILE_SEC,
                        record_size=fs, fs=fs, seed=seed)
    write_dataset(root, m, gen=gen)


# -- float64 host references -----------------------------------------------

@dataclasses.dataclass
class Reference:
    """SciPy/NumPy float64 features of one record."""

    x: np.ndarray              # the decoded waveform, float64
    welch: np.ndarray
    spl: float
    tol: np.ndarray
    frame_spl: np.ndarray      # per-frame wideband SPL, dB
    peak_bin: np.ndarray       # per-frame argmax PSD bin


def reference(x: np.ndarray, p) -> Reference:
    import scipy.signal as ss
    from repro.core.tol import band_matrix

    x = np.asarray(x, np.float64)
    _, welch = ss.welch(x, fs=p.fs, window=p.window,
                        nperseg=p.window_size, noverlap=p.window_overlap,
                        nfft=p.nfft, detrend=False, scaling="density")
    w = ss.get_window(p.window, p.window_size)
    frames = np.lib.stride_tricks.sliding_window_view(
        x, p.window_size)[::p.hop][:p.frames_per_record]
    psd = np.abs(np.fft.rfft(frames * w, n=p.nfft)) ** 2 \
        / (p.fs * np.sum(w * w))
    psd[:, 1:-1] *= 2.0                          # one-sided, nfft even
    frame_power = psd.sum(axis=-1) * p.df
    return Reference(
        x=x, welch=welch,
        spl=10.0 * np.log10(welch.sum() * p.df) + p.gain_db,
        tol=10.0 * np.log10(np.maximum(welch @ band_matrix(p) * p.df,
                                       1e-30)) + p.gain_db,
        frame_spl=10.0 * np.log10(np.maximum(frame_power, 1e-30))
        + p.gain_db,
        peak_bin=psd.argmax(axis=-1))


def detect(spl, peak_bin, thr: float, hyst: float, min_len: int):
    """Frame-by-frame Schmitt trigger: the detector's documented
    semantics (kernels/events.py) in float32 comparisons.  Returns the
    list of (onset, duration, peak_bin, peak_db) events."""
    spl = np.asarray(spl, np.float32)
    lo = np.float32(thr) - np.float32(hyst)
    events, open_ = [], False
    start = pk_db = pk = None
    for f, s in enumerate(spl):
        if open_ and s < lo:
            if f - start >= min_len:
                events.append((start, f - start, pk, pk_db))
            open_ = False
        if open_ and s > pk_db:
            pk_db, pk = s, peak_bin[f]
        if not open_ and s >= np.float32(thr):
            open_, start, pk_db, pk = True, f, s, peak_bin[f]
    if open_ and len(spl) - start >= min_len:
        events.append((start, len(spl) - start, pk, pk_db))
    return events


def impulsive(x: np.ndarray, onset: int, dur: int, p) -> np.ndarray:
    """float64 SEL, zero-to-peak level, kurtosis and rise time of one
    event's sample span."""
    s0 = onset * p.hop
    seg = x[s0:min((onset + dur - 1) * p.hop + p.window_size, len(x))]
    e = seg * seg
    m2 = np.mean((seg - seg.mean()) ** 2)
    m4 = np.mean((seg - seg.mean()) ** 4)
    return np.array([
        10.0 * np.log10(max(e.sum() / p.fs, 1e-30)) + p.gain_db,
        10.0 * np.log10(max(e.max(), 1e-30)) + p.gain_db,
        m4 / max(m2 * m2, 1e-30),
        float(np.argmax(e)) / p.fs])


def event_threshold(refs, param_set: int) -> tuple[float, float]:
    """The first threshold at or above the set's base level that no
    reference frame SPL comes within EVENT_MARGIN_DB of (open level or
    close level), so float32 rounding on the chip cannot flip a frame."""
    base, hyst = EVENT_LEVELS[param_set]
    for k in range(1000):
        thr = round(base + 0.01 * k, 2)
        lo = float(np.float32(thr) - np.float32(hyst))
        if all(np.abs(r.frame_spl - level).min() > EVENT_MARGIN_DB
               for r in refs.values() for level in (thr, lo)):
            return thr, hyst
    raise RuntimeError("no event threshold clears the reference frames")


# -- the compiled programs of a phase --------------------------------------

class StepPrograms:
    """Every step program the jobs inside the ``with`` block compile,
    with the argument shapes and shard placement of its first call.

    Every job and the service get their step from
    ``engine.compile_step``; it is wrapped for the block's duration.
    """

    def __init__(self):
        self.programs: list[dict] = []

    def __enter__(self):
        import jax
        from repro.api import engine

        self._engine, self._real = engine, engine.compile_step

        def build(*key):
            fn = self._real(*key)
            prog = {"fn": fn, "args": None}
            self.programs.append(prog)

            def step(*args):
                if prog["args"] is not None:
                    return fn(*args)
                prog["args"] = [jax.ShapeDtypeStruct(a.shape, a.dtype)
                                for a in args]
                prog["payload"] = [(s.device, s.data.shape)
                                   for s in args[0].addressable_shards]
                out = fn(*args)
                prog["outputs"] = {
                    jax.tree_util.keystr(path): [
                        (s.device, s.data.shape)
                        for s in leaf.addressable_shards]
                    for path, leaf in
                    jax.tree_util.tree_flatten_with_path(out)[0]}
                return out
            return step

        engine.compile_step = build
        return self

    def __exit__(self, *exc):
        self._engine.compile_step = self._real

    def check_kernels(self, expected: set[str]) -> list[str]:
        """Each program ran holds exactly one ``tpu_custom_call`` per
        kernel of ``expected``; returns the compiled HLO texts."""
        from repro.kernels.common import tpu_kernel_calls

        texts = []
        for prog in self.programs:
            if prog["args"] is None:
                continue
            text = prog["fn"].lower(*prog["args"]).compile().as_text()
            calls = tpu_kernel_calls(text)
            if calls != dict.fromkeys(expected, 1):
                raise AssertionError(
                    f"compiled step holds kernels {dict(calls)}, expected "
                    f"one each of {sorted(expected)}")
            texts.append(text)
        if not texts:
            raise AssertionError("no step program ran in this phase")
        say(f"  kernels: {len(texts)} step program(s), each with one "
            f"tpu_custom_call per kernel {sorted(expected)}")
        return texts


@contextlib.contextmanager
def phase(name: str, records: int, record_sec: float):
    """Times a phase: wall clock and XLA backend compile time."""
    import jax

    compile_s = [0.0]

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += duration

    jax.monitoring.register_event_duration_secs_listener(listen)
    t0 = time.perf_counter()
    try:
        yield
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    wall = time.perf_counter() - t0
    rt = f", {records * record_sec / wall:.1f}x real time" if records else ""
    say(f"phase {name}: wall {wall:.2f} s, compile {compile_s[0]:.2f} s, "
        f"{records} records{rt}")


# -- checks -----------------------------------------------------------------

def store_files(d: str) -> list[str]:
    return sorted(f for f in os.listdir(d) if f.endswith((".npy", ".bin")))


def assert_same_bytes(a: str, b: str, what: str) -> None:
    names = store_files(a)
    if names != store_files(b):
        raise AssertionError(f"{what}: stores hold different files")
    for n in names:
        if not filecmp.cmp(os.path.join(a, n), os.path.join(b, n),
                           shallow=False):
            raise AssertionError(f"{what}: {n} differs")
    say(f"  {what}: {len(names)} output files bitwise-equal")


def assert_same_results(a, b, what: str) -> None:
    """Every output namespace of two JobResults, bitwise."""
    for ns in ("features", "epoch", "windows"):
        da, db = getattr(a, ns) or {}, getattr(b, ns) or {}
        if sorted(da) != sorted(db):
            raise AssertionError(f"{what}: {ns} keys differ")
        for k in da:
            if not np.array_equal(da[k], db[k]):
                raise AssertionError(f"{what}: {ns}[{k!r}] differs")
    if sorted(a.events) != sorted(b.events):
        raise AssertionError(f"{what}: event logs differ")
    for k in a.events:
        if not (np.array_equal(a.events[k].counts, b.events[k].counts)
                and np.array_equal(a.events[k].rows, b.events[k].rows)):
            raise AssertionError(f"{what}: events[{k!r}] differ")
    say(f"  {what}: features, epoch, windows and events bitwise-equal")


def check_references(store: str, p, refs: dict, thr: float,
                     hyst: float) -> None:
    """The chip's stored outputs against the float64 references."""
    from repro import api
    from repro.core.store import FeatureStore

    welch = np.load(os.path.join(store, "welch.npy"), mmap_mode="r")
    spl = np.load(os.path.join(store, "spl.npy"), mmap_mode="r")
    tol = np.load(os.path.join(store, "tol.npy"), mmap_mode="r")
    fs_ = FeatureStore(store)
    logs = {name: api.EventLog(*fs_.load_events(name, len(cols)),
                               columns=cols, capacity=p.event_capacity)
            for name, cols in (("events", api.EVENT_COLUMNS),
                               ("impulsive", api.IMPULSIVE_COLUMNS))}
    for name in store_files(store):
        if name.endswith(".npy") and ".counts" not in name:
            if not np.isfinite(np.load(os.path.join(store, name),
                                       mmap_mode="r")).all():
                raise AssertionError(f"{name} holds non-finite values")
    err = dict.fromkeys(("welch_rel", "spl_db", "tol_db", "peak_db",
                         "sel_db", "peak_level_db", "kurtosis",
                         "rise_s"), 0.0)
    n_events = 0
    for i, r in refs.items():
        err["welch_rel"] = max(err["welch_rel"], float(
            np.max(np.abs(welch[i] - r.welch) / r.welch)))
        err["spl_db"] = max(err["spl_db"], abs(float(spl[i]) - r.spl))
        err["tol_db"] = max(err["tol_db"],
                            float(np.max(np.abs(tol[i] - r.tol))))
        want = detect(r.frame_spl, r.peak_bin, thr, hyst, p.event_min_len)
        got, vals = logs["events"].record(i), logs["impulsive"].record(i)
        if logs["events"].counts[i] != len(want):
            raise AssertionError(
                f"record {i}: {logs['events'].counts[i]} events on the "
                f"chip, {len(want)} in the reference")
        for row, val, (on, dur, pk, pk_db) in zip(got, vals, want):
            if (int(row[0]), int(row[1]), int(row[2])) != (on, dur, pk):
                raise AssertionError(
                    f"record {i}: event {row[:3]} on the chip, "
                    f"{(on, dur, pk)} in the reference")
            err["peak_db"] = max(err["peak_db"], abs(row[3] - pk_db))
            ref = impulsive(r.x, on, dur, p)
            err["sel_db"] = max(err["sel_db"], abs(val[0] - ref[0]))
            err["peak_level_db"] = max(err["peak_level_db"],
                                       abs(val[1] - ref[1]))
            # as np.testing.assert_allclose(rtol=atol=KURTOSIS_TOL):
            # the error in units of the allowed error
            err["kurtosis"] = max(err["kurtosis"], abs(val[2] - ref[2])
                                  / (KURTOSIS_TOL * (1.0 + abs(ref[2]))))
            err["rise_s"] = max(err["rise_s"], abs(val[3] - ref[3]))
        n_events += len(want)
    say(f"  reference errors over records {sorted(refs)} ({n_events} "
        f"events): " + ", ".join(f"{k} {float(v):.3g}"
                                 for k, v in err.items()))
    limits = {"welch_rel": WELCH_RTOL, "spl_db": LEVEL_TOL_DB,
              "tol_db": LEVEL_TOL_DB, "peak_db": PEAK_DB_TOL,
              "sel_db": IMPULSIVE_DB_TOL,
              "peak_level_db": IMPULSIVE_DB_TOL,
              "kurtosis": 1.0, "rise_s": 2.0 / p.fs}
    over = {k: float(err[k]) for k in limits if err[k] > limits[k]}
    if over:
        raise AssertionError(f"outside the repo's tolerances: {over} "
                             f"(limits {limits})")
    if n_events == 0:
        raise AssertionError("the checked records hold no event")


# -- phases -------------------------------------------------------------------

def params(param_set: int):
    from repro.core.params import PARAM_SET_1, PARAM_SET_2
    return PARAM_SET_1 if param_set == 1 else PARAM_SET_2


def references(data: str, p) -> tuple[int, dict]:
    """float64 references of the first, middle and last record."""
    from repro import api
    from repro.data.wavio import WavRecordReader

    m = api.scan_dataset(data, p.record_size, seed=42)
    reader = WavRecordReader(data, m)
    n = m.n_records
    return n, {i: reference(reader.read_one(i), p)
               for i in sorted({0, n // 2, n - 1})}


def depam_args(data: str, out: str, param_set: int, thr: float,
               hyst: float, payload: str = "int16") -> list[str]:
    return ["--data-root", data, "--out", out,
            "--param-set", str(param_set), "--payload", payload,
            "--features", ",".join(FEATURES), "--events",
            "--event-threshold-db", str(thr),
            "--event-hysteresis-db", str(hyst),
            "--window", "per-file", "--to", "store",
            "--chunk-records", str(CHUNK[param_set])]


def one_chip(data: str, out: str) -> None:
    from repro import api
    from repro.core.store import FeatureStore
    from repro.launch import depam_run, serve

    for param_set in (1, 2):
        p = params(param_set)
        n, refs = references(data, p)
        thr, hyst = event_threshold(refs, param_set)
        say(f"set {param_set}: {n} records of {p.record_size_sec:g} s, "
            f"events at {thr} dB (hysteresis {hyst} dB)")
        store = os.path.join(out, f"set{param_set}-int16")
        with StepPrograms() as progs, \
                phase(f"set{param_set}-int16", n, p.record_size_sec):
            depam_run.main(depam_args(data, store, param_set, thr, hyst))
        progs.check_kernels(KERNELS[param_set])
        check_references(store, p, refs, thr, hyst)
        if param_set != 1:
            continue

        other = os.path.join(out, "set1-float32")
        with StepPrograms() as progs, \
                phase("set1-float32", n, p.record_size_sec):
            depam_run.main(depam_args(data, other, 1, thr, hyst,
                                      payload="float32"))
        progs.check_kernels(KERNELS[1])
        assert_same_bytes(store, other, "float32 vs int16 transport")

        # stopped half way, then resumed by the same CLI command: the
        # first half is the job depam_run builds, cut by .limit()
        resumed = os.path.join(out, "set1-resumed")
        m = api.scan_dataset(data, p.record_size, seed=42)
        half = -(-n // CHUNK[1]) // 2
        with phase("set1-stop-resume", n, p.record_size_sec):
            (api.job(m, p).features(*FEATURES).chunk(CHUNK[1])
             .to(FeatureStore(resumed)).window(per_file=True)
             .source(api.WavSource(data)).payload("int16")
             .events(thr, hysteresis_db=hyst, impulsive=True)
             .async_io(depth=2).limit(half).run())
            depam_run.main(depam_args(data, resumed, 1, thr, hyst))
        assert_same_bytes(store, resumed, f"stopped at step {half} and "
                                          f"resumed vs uninterrupted")

    with StepPrograms() as progs, phase("service", 24, 60.0):
        results, _ = serve.run(tenants=2, live=1, param_set=1,
                               record_sec=60.0, records_per_file=4,
                               verify=True)
    progs.check_kernels({"welch_psd"})
    for name, r in sorted(results.items()):
        if not np.isfinite(r["welch"]).all():
            raise AssertionError(f"service tenant {name}: non-finite welch")


def four_chips(data: str) -> None:
    from repro import api
    from repro.launch.mesh import make_host_mesh

    p = params(1)
    n, refs = references(data, p)
    thr, hyst = event_threshold(refs, 1)
    m = api.scan_dataset(data, p.record_size, seed=42)
    mesh = make_host_mesh(data=4)
    say(f"set 1 at .shards(4): {n} records of {p.record_size_sec:g} s on "
        f"one chip, then on a mesh of {len(mesh.devices.flat)} chips")

    def job():
        return (api.job(m, p).features(*FEATURES).chunk(CHUNK[1])
                .window(per_file=True).source(api.WavSource(data))
                .payload("int16")
                .events(thr, hysteresis_db=hyst, impulsive=True)
                .async_io(depth=2).shards(4))

    with StepPrograms() as progs, phase("set1-one-chip", n, 60.0):
        single = job().run()
    progs.check_kernels(KERNELS[1])
    with StepPrograms() as progs, phase("set1-four-chips", n, 60.0):
        sharded = job().on(mesh).run()
    texts = progs.check_kernels(KERNELS[1])
    assert_same_results(single, sharded, "4-chip mesh vs one chip")

    devices = set(mesh.devices.flat)
    for prog, text in zip(progs.programs, texts):
        placed = {"payload": prog["payload"], **prog["outputs"]}
        for name, shards in placed.items():
            on = {d for d, _ in shards}
            if on != devices or len(shards) != 4 \
                    or any(shape[0] != 1 for _, shape in shards):
                raise AssertionError(
                    f"{name}: shards {[(d.id, s) for d, s in shards]} "
                    f"are not one per device of the mesh")
        for op in ("all-gather", "all-reduce", "all-to-all",
                   "collective-permute", "reduce-scatter"):
            if op in text:
                raise AssertionError(f"the sharded step holds {op}")
    say(f"  work split: payload and {len(placed) - 1} per-record outputs "
        f"one shard per device on {sorted(d.id for d in devices)}; the "
        f"step holds no collective")


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated wav files")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the set-1 job on a 4-chip mesh and "
                         "on one chip, and compare them bitwise")
    a = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devices[0].platform!r}); this smoke runs on the chip "
                 f"only")
    count = 4 if a.four_chips else len(devices)
    if len(devices) < count:
        sys.exit(f"chip_smoke: --four-chips needs 4 devices, JAX found "
                 f"{len(devices)}")

    sys.path.insert(0, str(ROOT / "src"))
    from repro.kernels import common
    from repro.launch import runtime

    if common.use_interpret():
        sys.exit("chip_smoke: the Pallas kernels would run in interpret "
                 "mode")
    runtime.enable_compile_cache()
    say("smoke run of the DEPAM job, not a benchmark")
    say(runtime.device_line())

    with tempfile.TemporaryDirectory(prefix="depam-smoke-data-") as data, \
            tempfile.TemporaryDirectory(prefix="depam-smoke-out-") as out:
        with phase("data", 0, 0.0):
            write_data(data, a.seed)
        say(f"  {N_FILES} wav files of {FILE_SEC} s at 32768 Hz, "
            f"{sum(os.path.getsize(os.path.join(data, f)) for f in os.listdir(data)) / 1e6:.0f} MB")
        if a.four_chips:
            four_chips(data)
        else:
            one_chip(data, out)

    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": count}}))


if __name__ == "__main__":
    main()
