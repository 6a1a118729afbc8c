"""The feature registry: what the engine knows how to compute.

A :class:`FeatureSpec` is the unit of extensibility.  It declares

  * ``shape(manifest, params)`` — the per-record trailing shape, which is
    all the store needs to lay out its memmap; ``None`` marks a
    *reduction-only* feature (``ltsa``/``spd`` below): its per-chunk
    value feeds reductions but is never stored per record;
  * ``compute(ctx)`` — a traceable function from the shared
    :class:`FeatureContext` (records + cached Welch / frame-PSD
    intermediates) to a ``(batch, *shape)`` array;
  * ``fill`` — the value written into padding slots beyond the manifest
    end (0 for linear power, -inf for dB levels);
  * optional ``setup(manifest, params)`` — host-side constants (e.g. the
    TOL band matrix) baked into the jitted step;
  * optional ``reductions`` — :class:`Reduction` instances turning the
    per-record value into windowed soundscape products (LTSA panels,
    SPD histograms, spectrum extrema) or whole-epoch aggregates, all
    accumulated in the engine's on-device multi-window carry.

Because every selected spec computes from the SAME context inside ONE
jitted step, features compose in a single pass over the data and share
intermediates: selecting ("welch", "spl", "tol") runs the Welch PSD once,
and ("welch", "ltsa", "spd") reduces LTSA/SPD from the same Welch /
frame-PSD traces that produce the per-record arrays.

Registering a new feature requires no engine, store, or CLI changes —
``percentiles`` below is the proof: pypam-style per-record spectrum
percentile statistics added purely through this registry.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import numpy as np

import jax.numpy as jnp

from repro.core import spectra
from repro.core.manifest import DatasetManifest
from repro.core.params import DepamParams
from repro.core.tol import band_matrix as make_band_matrix
from repro.kernels import ops, select


class FeatureContext:
    """Shared per-trace state handed to every ``FeatureSpec.compute``.

    ``records`` is the flat ``(batch, record_size)`` float32 waveform
    batch on one device.  Expensive intermediates (Welch PSD, per-frame
    PSD) are computed lazily and cached, so N features selecting the
    same intermediate trace it exactly once.

    With the int16 payload transport the context is constructed from the
    raw ``(batch, record_size)`` PCM plus the per-record decode-scale
    sidecar (``scales``).  The PSD intermediates then hand the PCM
    straight to the Pallas kernels, which dequantize in VMEM — the
    float32 waveform never exists in HBM.  ``ctx.records`` stays
    available for features that need the waveform itself: it
    dequantizes lazily (bitwise-equal to the host decode) and only
    features that touch it pay for the materialization.
    """

    def __init__(self, records: jnp.ndarray, params: DepamParams,
                 use_kernels: bool, consts: dict[str, dict],
                 scales: jnp.ndarray | None = None):
        self.quantized = records.dtype == jnp.int16
        self.pcm = records if self.quantized else None
        self.scales = scales
        self.params = params
        self.use_kernels = use_kernels
        self._consts = consts
        self._cache: dict[str, jnp.ndarray] = {}
        if not self.quantized:
            self._cache["records"] = records

    def const(self, feature: str, name: str) -> jnp.ndarray:
        """A host-side constant declared by ``FeatureSpec.setup``."""
        return self._consts[feature][name]

    @property
    def records(self) -> jnp.ndarray:
        """(batch, record_size) float32 waveforms (lazy dequantize)."""
        if "records" not in self._cache:
            from repro.kernels.common import dequantize
            self._cache["records"] = dequantize(self.pcm, self.scales)
        return self._cache["records"]

    def _psd(self, key: str, kernel_fn, xla_fn) -> jnp.ndarray:
        """Shared dispatch for the cached PSD intermediates: the Pallas
        entry points take raw PCM + the scales sidecar directly (dequant
        happens in VMEM); the XLA fallback gets the (lazily
        dequantized) float32 records."""
        if key not in self._cache:
            if self.use_kernels:
                src = self.pcm if self.quantized else self.records
                out = kernel_fn(src, self.params,
                                scales=self.scales
                                if self.quantized else None)
            else:
                out = xla_fn(self.records, self.params)
            self._cache[key] = out
        return self._cache[key]

    @property
    def welch(self) -> jnp.ndarray:
        """(batch, n_bins) Welch PSD, Pallas kernel or XLA path."""
        return self._psd("welch", ops.welch_psd, spectra.welch_psd)

    @property
    def frame_psd(self) -> jnp.ndarray:
        """(batch, n_frames, n_bins) per-frame PSD (the spectrogram)."""
        return self._psd("frame_psd", ops.frame_psd, spectra.frame_psd)

    @property
    def frame_spl(self) -> jnp.ndarray:
        """(batch, n_frames) wideband SPL per analysis frame, dB — the
        detection trace the events kernel scans.  Rides the cached
        frame-PSD, so detection is a free rider on any job already
        computing the spectrogram."""
        if "frame_spl" not in self._cache:
            p = self.params
            power = jnp.sum(self.frame_psd, axis=-1) * p.df
            self._cache["frame_spl"] = (
                10.0 * jnp.log10(jnp.maximum(power, 1e-30)) + p.gain_db)
        return self._cache["frame_spl"]

    @property
    def frame_peak_bin(self) -> jnp.ndarray:
        """(batch, n_frames) int32 argmax PSD bin per frame."""
        if "frame_peak_bin" not in self._cache:
            self._cache["frame_peak_bin"] = jnp.argmax(
                self.frame_psd, axis=-1).astype(jnp.int32)
        return self._cache["frame_peak_bin"]

    @property
    def events(self) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Detected events, cached so ``events`` and ``impulsive`` share
        one scan: ``(counts (batch,) int32, rows (batch, event_capacity,
        4) float32)`` with rows ``(onset_frame, n_frames, peak_bin,
        peak_db)``.  Thresholds come off ``ctx.params``."""
        if "events" not in self._cache:
            self._cache["events"] = ops.detect_events(
                self.frame_spl, self.frame_peak_bin, self.params,
                kernel=self.use_kernels)
        return self._cache["events"]


# ---------------------------------------------------------------------------
# Windows & reductions — the multi-resolution reduction protocol.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Window:
    """A named partition of the record index space into time windows.

    Three concrete kinds plus one late-binding sentinel:

      * ``records`` — fixed-size windows of ``records`` consecutive
        records (the last window may be partial);
      * ``file`` — one window per manifest file (hourly/daily products
        when files are deployments' natural chunks);
      * ``epoch`` — the degenerate single window covering everything;
      * ``job`` — resolved by the engine to whatever the job builder's
        ``.window(...)`` selected (``epoch`` when unset).  Built-in
        windowed reductions declare this, so ONE registry entry serves
        every resolution.

    Windows follow the plan's global record order, so they close as the
    committed cursor advances — that is what lets the engine flush
    finished windows to the sink mid-job.
    """

    kind: str                      # "epoch" | "records" | "file" | "job"
    records: int | None = None

    def __post_init__(self):
        if self.kind not in ("epoch", "records", "file", "job"):
            raise ValueError(f"unknown window kind {self.kind!r}")
        if (self.kind == "records") != (self.records is not None):
            raise ValueError("records= is required for (exactly) the "
                             "'records' window kind")
        if self.records is not None and self.records < 1:
            raise ValueError(f"window records must be >= 1, "
                             f"got {self.records}")

    @property
    def key(self) -> str:
        """Stable name, e.g. ``records:512`` — used in error messages
        and as the engine's window-id routing key."""
        return f"records:{self.records}" if self.kind == "records" \
            else self.kind

    def edges(self, m: DatasetManifest) -> np.ndarray:
        """Record-offset boundaries, shape (n_windows + 1,): window ``i``
        covers global records [edges[i], edges[i+1])."""
        if self.kind == "epoch":
            return np.asarray([0, m.n_records], np.int64)
        if self.kind == "records":
            n = int(np.ceil(max(m.n_records, 1) / self.records))
            e = np.arange(n + 1, dtype=np.int64) * self.records
            e[-1] = m.n_records
            return e
        if self.kind == "file":
            return np.asarray(m.file_offsets, np.int64)
        raise ValueError("the 'job' window must be resolved by the "
                         "engine before use")

    def n_windows(self, m: DatasetManifest) -> int:
        return len(self.edges(m)) - 1

    def ids(self, indices: np.ndarray, m: DatasetManifest) -> np.ndarray:
        """Global record indices -> window ids (host-side, per step).
        Padding indices beyond the manifest clamp to the last window —
        their contributions are masked to the identity anyway."""
        idx = np.minimum(np.asarray(indices, np.int64),
                         max(m.n_records - 1, 0))
        if self.kind == "epoch":
            return np.zeros(idx.shape, np.int32)
        if self.kind == "records":
            return (idx // self.records).astype(np.int32)
        e = self.edges(m)
        return (np.searchsorted(e, idx, side="right") - 1).astype(np.int32)


EPOCH_WINDOW = Window("epoch")
JOB_WINDOW = Window("job")


@dataclasses.dataclass(frozen=True)
class StateField:
    """One named array in a reduction's per-window carry state.

    ``merge`` names the associative combine the engine applies — within
    a step (a segment reduce over the records that hit each window),
    across steps (carry ⊕ step partial), and across the mesh (the
    collective a replicated out-sharding inserts):

      * ``"sum"`` — plain addition;
      * ``"ksum"`` — Kahan-compensated float32 addition: the engine
        carries a companion compensation array under ``<key>:c`` so
        accumulation error stays O(eps) at any step count, and hands
        ``finalize`` the already-corrected sum;
      * ``"min"`` / ``"max"`` — elementwise extrema.

    ``init`` is the merge identity (0 for sums, ±inf for extrema);
    ``dtype`` is ``"float32"`` or ``"int32"`` (exact counts).
    """

    name: str
    shape: tuple[int, ...] = ()
    merge: str = "sum"
    dtype: str = "float32"
    init: float = 0.0

    def __post_init__(self):
        if self.merge not in ("sum", "ksum", "min", "max"):
            raise ValueError(f"unknown merge op {self.merge!r}")
        if self.dtype not in ("float32", "int32"):
            raise ValueError(f"unsupported state dtype {self.dtype!r}")
        if self.merge == "ksum" and self.dtype != "float32":
            raise ValueError("ksum compensation is float32-only")


@dataclasses.dataclass(frozen=True)
class Reduction:
    """A windowed (or epoch) reduction over a feature's per-record value.

    The init/update/merge/finalize protocol:

      * ``init(manifest, params)`` — declares the per-window carry
        layout as a tuple of :class:`StateField` (shape, identity, and
        the associative *merge* op per field);
      * ``update(value, mask)`` — traceable; maps the feature's flat
        ``(batch, ...)`` step value + live-mask to per-record
        contributions ``{field: (batch, *field.shape)}`` (masked slots
        must contribute the field's identity);
      * *merge* — declarative, per field (see :class:`StateField`): the
        engine segment-reduces contributions into window slots and
        merges them into the on-device carry, which also makes resumed
        accumulation bitwise-exact (the carry rides commit state);
      * ``finalize(state)`` — host-side, row-wise over windows: maps the
        float64 copy of the carry (``ksum`` fields arrive
        compensation-corrected) to the published
        ``(n_windows, *out_shape)`` array.  Row-wise purity is what lets
        the engine flush closed windows incrementally mid-job.

    ``window`` is where the reduction accumulates: the module-level
    :data:`JOB_WINDOW` (default — the job builder's ``.window(...)``
    choice) or an explicit window such as :data:`EPOCH_WINDOW`
    (``welch``'s ``mean_welch`` below, published via ``JobResult.epoch``
    with the single-window axis squeezed; everything else lands in
    ``JobResult.windows``).
    """

    out_name: str
    init: Callable[[DatasetManifest, DepamParams], tuple[StateField, ...]]
    update: Callable[[jnp.ndarray, jnp.ndarray], dict[str, jnp.ndarray]]
    finalize: Callable[[dict[str, np.ndarray]], np.ndarray]
    out_shape: Callable[[DatasetManifest, DepamParams], tuple[int, ...]]
    window: Window = JOB_WINDOW
    doc: str = ""


@dataclasses.dataclass(frozen=True)
class FeatureSpec:
    """A registered feature workload (see module docstring).

    ``ragged=True`` marks the third output kind beside fixed-shape and
    reduction-only: ``compute`` returns a count-prefixed pair
    ``(counts (batch,) int32, rows (batch, capacity, len(columns))
    float32)`` instead of a dense array.  ``counts`` is the TRUE
    per-record event count (``counts > capacity`` flags overflow), and
    the engine routes the host-compacted rows to the sink's append-only
    event log rather than a per-record memmap.  Ragged specs must name
    their ``columns`` and cannot also declare reductions or a dense
    ``shape``.
    """

    name: str
    shape: Callable[[DatasetManifest, DepamParams],
                    tuple[int, ...]] | None
    compute: Callable[[FeatureContext], jnp.ndarray]
    fill: float = 0.0
    setup: Callable[[DatasetManifest, DepamParams], dict] | None = None
    reductions: tuple[Reduction, ...] = ()
    ragged: bool = False
    columns: tuple[str, ...] = ()
    doc: str = ""

    def __post_init__(self):
        if self.ragged:
            if not self.columns:
                raise ValueError(
                    f"ragged feature {self.name!r} must declare columns")
            if self.shape is not None or self.reductions:
                raise ValueError(
                    f"ragged feature {self.name!r} cannot also declare a "
                    f"dense shape or reductions")
        elif self.columns:
            raise ValueError(
                f"feature {self.name!r}: columns= is only meaningful "
                f"with ragged=True")


_REGISTRY: dict[str, FeatureSpec] = {}


def register(spec: FeatureSpec, *, overwrite: bool = False) -> FeatureSpec:
    """Add a feature to the registry; returns the spec for chaining."""
    if spec.name in _REGISTRY and not overwrite:
        raise ValueError(
            f"feature {spec.name!r} already registered "
            f"(pass overwrite=True to replace)")
    _REGISTRY[spec.name] = spec
    return spec


def unregister(name: str) -> None:
    _REGISTRY.pop(name, None)


def get_feature(name: str) -> FeatureSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown feature {name!r}; registered: "
                       f"{sorted(_REGISTRY)}") from None


def feature_names() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def resolve_features(feats: Sequence[str | FeatureSpec]) -> list[FeatureSpec]:
    """Names and/or inline specs -> specs, order preserved, no dups."""
    out: list[FeatureSpec] = []
    seen: set[str] = set()
    for f in feats:
        spec = f if isinstance(f, FeatureSpec) else get_feature(f)
        if spec.name in seen:
            raise ValueError(f"feature {spec.name!r} selected twice")
        seen.add(spec.name)
        out.append(spec)
    return out


# ---------------------------------------------------------------------------
# Built-in features — the paper's workload, as registry entries.
# ---------------------------------------------------------------------------

def _finalize_mean(state: dict[str, np.ndarray]) -> np.ndarray:
    """sum/count per window; windows that never saw a record (possible
    under per-file windows with empty files) publish NaN, not 0."""
    count = state["count"][..., None]
    mean = state["sum"] / np.maximum(count, 1.0)
    return np.where(count > 0, mean, np.nan)


def mean_reduction(out_name: str, n_cols, *, window: Window = JOB_WINDOW,
                   kahan: bool = False, doc: str = "") -> Reduction:
    """Windowed mean of a ``(batch, n_cols)`` feature value.

    ``n_cols`` is a ``(manifest, params) -> int`` callable (or an int).
    ``kahan=True`` compensates the float32 sums (the whole-epoch mean
    wants it; bounded windows usually don't need the extra state).
    """
    cols = n_cols if callable(n_cols) else (lambda m, p: n_cols)
    return Reduction(
        out_name=out_name,
        init=lambda m, p: (
            StateField("sum", (cols(m, p),),
                       merge="ksum" if kahan else "sum"),
            StateField("count", (), merge="sum", dtype="int32")),
        update=lambda v, mask: {
            "sum": v * mask[:, None].astype(v.dtype),
            "count": mask.astype(jnp.int32)},
        finalize=_finalize_mean,
        out_shape=lambda m, p: (cols(m, p),),
        window=window, doc=doc)


register(FeatureSpec(
    name="welch",
    shape=lambda m, p: (p.n_bins,),
    compute=lambda ctx: ctx.welch,
    fill=0.0,
    reductions=(mean_reduction(
        "mean_welch", lambda m, p: p.n_bins, window=EPOCH_WINDOW,
        kahan=True,
        doc="Epoch mean Welch PSD (the paper's final join)."),),
    doc="Per-record Welch PSD (linear, scipy 'density' scaling)."))


register(FeatureSpec(
    name="spl",
    shape=lambda m, p: (),
    compute=lambda ctx: spectra.spl_wideband(ctx.welch, ctx.params),
    fill=-float("inf"),
    doc="Wideband SPL per record, dB re 1 uPa."))


register(FeatureSpec(
    name="tol",
    shape=lambda m, p: (make_band_matrix(p).shape[1],),
    setup=lambda m, p: {"band_matrix": make_band_matrix(p)},
    compute=lambda ctx: (
        (ops.tol_levels if ctx.use_kernels else spectra.tol_levels)(
            ctx.welch, ctx.const("tol", "band_matrix"), ctx.params)),
    fill=-float("inf"),
    doc="Third-octave levels per record, dB (IEC 61260 base-10 bands)."))


# pypam-style soundscape statistics: per-record percentiles of the frame
# spectrogram (dB), per frequency bin.  The extensibility proof — a new
# workload added with zero engine/store edits.  Each level interpolates
# two order statistics of a (record, bin) column; the kernel path selects
# them (kernels/select.py) instead of sorting the column, and returns the
# elements the sort would, so either way the levels have the same bits.
SPECTRUM_PERCENTILES = (1.0, 5.0, 25.0, 50.0, 75.0, 95.0, 99.0)
# Records of at least this many frames select their order statistics;
# shorter ones sort.  On a TPU v5e the sort costs ~8.8 ps per element and
# bitonic stage, log2(F)(log2(F) + 1)/2 stages for F frames padded to a
# power of 2: 14.6 ms a step at set 1 (15,359 frames, 8 x 129 columns)
# and 1.2 ms at set 2 (80 frames, 32 x 2,049 columns).  The selection
# makes 33 passes at any F, over frames padded to 256, at a rate not yet
# measured on the chip; the threshold sits where the sort's cost per
# element is half set 1's, and leaves set 2 to the sort.
PCT_SELECT_MIN_FRAMES = 1024


def selects_order_stats(n_frames: int) -> bool:
    """Whether the percentiles of ``n_frames``-frame records select their
    order statistics (else they sort)."""
    return n_frames >= PCT_SELECT_MIN_FRAMES


def _percentile_taps(n: int):
    """np.percentile's 'linear' interpolation over ``n`` sorted values,
    worked out on the host: (low index, high index, low weight, high
    weight) per SPECTRUM_PERCENTILES entry.

    Left to XLA (as ``jnp.percentile`` does), this arithmetic on
    constants is folded differently by different compilations of the
    same step — on a TPU, the shard_map step of a mesh and the one-chip
    step disagreed in the last bit of the 95th percentile's weight — so
    the step would not be bitwise-identical across device counts."""
    pos = np.asarray(SPECTRUM_PERCENTILES, np.float64) / 100.0 * (n - 1)
    lo = np.floor(pos).astype(np.int64)
    hi = np.minimum(lo + 1, n - 1)
    w = pos - lo
    return lo, hi, (1.0 - w).astype(np.float32), w.astype(np.float32)


def _percentiles_compute(ctx: FeatureContext) -> jnp.ndarray:
    p = ctx.params
    db = 10.0 * jnp.log10(jnp.maximum(ctx.frame_psd, 1e-30)) + p.gain_db
    lo, hi, w_lo, w_hi = _percentile_taps(db.shape[-2])
    if ctx.use_kernels and selects_order_stats(db.shape[-2]):
        v_lo, v_hi = select.select_order_stats(db, tuple(lo.tolist()),
                                               tuple(hi.tolist()))
        nan = jnp.any(jnp.isnan(db), axis=-2, keepdims=True)
    else:
        srt = jnp.sort(db, axis=-2)            # (batch, n_frames, n_bins)
        v_lo, v_hi = srt[:, lo], srt[:, hi]
        nan = jnp.isnan(srt[:, -1:])           # NaNs sort last
    pct = v_lo * w_lo[:, None] + v_hi * w_hi[:, None]
    # (batch, n_pct, n_bins); a bin with any NaN frame is NaN at every
    # level, as in jnp.percentile
    return jnp.where(nan, jnp.nan, pct)


register(FeatureSpec(
    name="percentiles",
    shape=lambda m, p: (len(SPECTRUM_PERCENTILES), p.n_bins),
    compute=_percentiles_compute,
    fill=-float("inf"),
    doc="Spectrum percentile levels per record (dB), pypam-style."))


# ---------------------------------------------------------------------------
# Windowed soundscape products — the multi-resolution workloads (all
# reduction-only: shape=None, nothing stored per record).  They compute
# from the SAME cached Welch / frame-PSD intermediates as welch/spl/tol/
# percentiles, so adding them to a job costs one reduction, not a second
# pass over the data.
# ---------------------------------------------------------------------------

register(FeatureSpec(
    name="ltsa",
    shape=None,
    compute=lambda ctx: ctx.welch,
    reductions=(mean_reduction(
        "ltsa", lambda m, p: p.n_bins,
        doc="Windowed mean Welch PSD — the long-term spectral average "
            "panel (linear; 10*log10 for the dB plot)."),),
    doc="LTSA: mean Welch PSD per time window (the paper's long-term "
        "averaged soundscape representation)."))


# SPD histogram layout (pypam compute_spd): dB bins of width SPD_DB_STEP
# spanning [SPD_DB_MIN, SPD_DB_MAX), per frequency bin, per window.
# Out-of-range frames are dropped, exactly like np.histogram's range=.
SPD_DB_MIN = -120.0
SPD_DB_MAX = 60.0
SPD_DB_STEP = 3.0
SPD_N_DB = int(round((SPD_DB_MAX - SPD_DB_MIN) / SPD_DB_STEP))


def _spd_db(ctx: FeatureContext) -> jnp.ndarray:
    p = ctx.params
    return 10.0 * jnp.log10(jnp.maximum(ctx.frame_psd, 1e-30)) + p.gain_db


def _spd_update(db: jnp.ndarray, mask: jnp.ndarray) -> dict:
    """Per-record frame-count histogram: (batch, n_frames, n_bins) dB ->
    {counts: (batch, n_bins, SPD_N_DB) int32}, counted densely: for each
    dB bin, the frames whose bin it is.  XLA fuses the compare into the
    sum over frames, so the (batch, n_frames, n_bins, SPD_N_DB) one-hot
    never exists and the cost is n_frames * n_bins * SPD_N_DB compares
    whatever the data; a scatter-add would serialise the many frames a
    noise floor puts into one bin.  Records stay independent, so a batch
    sharded over a mesh is counted where it lies.

    Invalid frames (out of range, NaN, masked record) take bin -1, which
    no bin matches.  A frame a hair below SPD_DB_MAX can round to bin
    SPD_N_DB in float32; it is counted where its flat ``freq * SPD_N_DB
    + dbin`` id points, in the next frequency's lowest bin (dropped at
    the last frequency), so committed histograms keep their bits."""
    dbin = jnp.floor((db - SPD_DB_MIN) / SPD_DB_STEP).astype(jnp.int32)
    valid = ((db >= SPD_DB_MIN) & (db < SPD_DB_MAX)
             & mask[:, None, None])
    dbin = jnp.where(valid, dbin, -1)
    counts = jnp.sum(dbin[..., None] == jnp.arange(SPD_N_DB + 1), axis=1,
                     dtype=jnp.int32)     # (batch, n_bins, SPD_N_DB + 1)
    spill = jnp.pad(counts[:, :-1, SPD_N_DB:],
                    ((0, 0), (1, 0), (0, SPD_N_DB - 1)))
    return {"counts": counts[..., :SPD_N_DB] + spill}


def _spd_finalize(state: dict[str, np.ndarray]) -> np.ndarray:
    """Counts -> empirical probability density per (window, freq bin):
    rows integrate to 1 over dB (np.histogram density=True semantics,
    normalized by the in-range frame count per frequency bin)."""
    counts = state["counts"]
    total = counts.sum(axis=-1, keepdims=True)
    return counts / np.where(total > 0, total * SPD_DB_STEP, 1.0)


register(FeatureSpec(
    name="spd",
    shape=None,
    compute=_spd_db,
    reductions=(Reduction(
        out_name="spd",
        init=lambda m, p: (
            StateField("counts", (p.n_bins, SPD_N_DB), dtype="int32"),),
        update=_spd_update,
        finalize=_spd_finalize,
        out_shape=lambda m, p: (p.n_bins, SPD_N_DB),
        doc="Spectral probability density: per-window histogram of the "
            "frame-PSD dB levels, per frequency bin (pypam "
            "compute_spd)."),),
    doc="SPD: windowed dB-histogram of the frame spectrogram, "
        "normalized to a probability density per frequency bin."))


def _extremum_reduction(out_name: str, op: str) -> Reduction:
    sign = np.inf if op == "min" else -np.inf

    def update(v, mask, _sign=np.float32(sign)):
        return {op: jnp.where(mask[:, None], v, _sign),
                "count": mask.astype(jnp.int32)}

    def finalize(state):
        count = state["count"][..., None]
        return np.where(count > 0, state[op], np.nan)

    return Reduction(
        out_name=out_name,
        init=lambda m, p: (
            StateField(op, (p.n_bins,), merge=op, init=sign),
            StateField("count", (), merge="sum", dtype="int32")),
        update=update,
        finalize=finalize,
        out_shape=lambda m, p: (p.n_bins,),
        doc=f"Windowed {op} Welch spectrum.")


register(FeatureSpec(
    name="minmax",
    shape=None,
    compute=lambda ctx: ctx.welch,
    reductions=(_extremum_reduction("min_welch", "min"),
                _extremum_reduction("max_welch", "max")),
    doc="Windowed min/max Welch spectrum per frequency bin (soundscape "
        "envelope statistics)."))


# ---------------------------------------------------------------------------
# Ragged detection workloads (pypam loud_event_detector / pile-driving
# impulsive metrics).  Both ride the cached frame-PSD trace and share
# ONE threshold+compaction scan via ctx.events, so selecting both costs
# a single detection pass.
# ---------------------------------------------------------------------------

EVENT_COLUMNS = ("onset", "duration", "peak_bin", "peak_db")
IMPULSIVE_COLUMNS = ("sel", "peak", "kurtosis", "rise_time")


register(FeatureSpec(
    name="events",
    shape=None,
    compute=lambda ctx: ctx.events,
    ragged=True,
    columns=EVENT_COLUMNS,
    doc="Loud-event windows per record (pypam loud_event_detector): "
        "Schmitt-trigger detection over the per-frame wideband SPL, "
        "rows = (onset_frame, n_frames, peak_bin, peak_db)."))


def _impulsive_compute(ctx: FeatureContext):
    """Per-event impulsive metrics from the raw waveform (pypam
    pile-driving suite): SEL, zero-to-peak level, kurtosis, rise time.

    Each detected event's sample span is [onset*hop,
    (onset+dur-1)*hop + window_size) clipped to the record — the samples
    its SPL frames actually covered.  The moment sums go through
    einsum (gemm) over a (batch, capacity, record_size) span mask
    rather than fused elementwise reductions: XLA materializes gemm
    operands, so the accumulation order cannot change with the
    surrounding program — that is what keeps the int16-payload program
    (decode multiply in-graph) bitwise-identical to the float32 one.
    Kurtosis therefore uses the algebraic central-moment identities
    over raw power sums (fine in float32 here: events are zero-mean-ish
    acoustic pressure, so the cancellation is mild, and the test oracle
    is float64).  O(capacity) memory blow-up over the waveform —
    fine at engine chunk sizes, entirely on-device, so only capacity
    rows come home.
    """
    p = ctx.params
    counts, rows = ctx.events
    x = ctx.records                                   # (B, N) float32
    n = x.shape[-1]
    k = p.event_capacity
    onset = rows[..., 0].astype(jnp.int32)            # (B, K) frames
    dur = rows[..., 1].astype(jnp.int32)
    valid = jnp.arange(k, dtype=jnp.int32)[None, :] \
        < jnp.minimum(counts, k)[:, None]
    s0 = onset * p.hop                                # first sample
    s1 = jnp.minimum((onset + dur - 1) * p.hop + p.window_size,
                     n)                               # one past last
    idx = jnp.arange(n, dtype=jnp.int32)[None, None, :]
    span = ((idx >= s0[..., None]) & (idx < s1[..., None])
            & valid[..., None])                       # (B, K, N) bool
    spanf = span.astype(jnp.float32)
    x2 = x * x
    pows = (x, x2, x2 * x, x2 * x2)
    ns, (S1, S2, S3, S4) = jnp.einsum('bkn->bk', spanf), tuple(
        jnp.einsum('bn,bkn->bk', v, spanf) for v in pows)
    nz = jnp.maximum(ns, 1.0)
    # SEL: 10 log10( integral of x^2 dt ), dB re 1 uPa^2 s
    sel = 10.0 * jnp.log10(jnp.maximum(S2 / jnp.float32(p.fs),
                                       1e-30)) + p.gain_db
    # zero-to-peak level
    x2m = jnp.where(span, x2[:, None, :], 0.0)
    pk2 = jnp.max(x2m, axis=-1)
    peak = 10.0 * jnp.log10(jnp.maximum(pk2, 1e-30)) + p.gain_db
    # kurtosis (m4/m2^2, non-Fisher) via central-moment identities
    mean = S1 / nz
    m2 = S2 / nz - mean * mean
    m4 = (S4 / nz - 4.0 * mean * (S3 / nz)
          + 6.0 * (mean * mean) * (S2 / nz)
          - 3.0 * (mean * mean) * (mean * mean))
    kurt = m4 / jnp.maximum(m2 * m2, 1e-30)
    # rise time: onset sample -> absolute-peak sample, seconds
    rise = (jnp.argmax(x2m, axis=-1).astype(jnp.float32)
            - s0.astype(jnp.float32)) / jnp.float32(p.fs)
    vals = jnp.stack([sel, peak, kurt, rise], axis=-1)
    return counts, jnp.where(valid[..., None], vals, 0.0)


register(FeatureSpec(
    name="impulsive",
    shape=None,
    compute=_impulsive_compute,
    ragged=True,
    columns=IMPULSIVE_COLUMNS,
    doc="Per-event impulsive metrics from the raw waveform (pypam "
        "pile-driving suite): SEL (dB re 1 uPa^2 s), zero-to-peak level "
        "(dB), kurtosis (m4/m2^2), rise time (s)."))
