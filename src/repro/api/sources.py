"""Record sources — where waveforms come from.

Two execution modes, unified behind one interface:

  * **device-synthesized** (``SynthSource``): the step function receives
    record *indices* and regenerates waveforms on-device from the
    manifest seed — byte-exact Spark-lineage recompute semantics (any
    worker can regenerate any record) and zero host IO;
  * **host-fed** (``ReaderSource`` / ``WavSource``): the driver fetches
    ``(n_shards, chunk, record_size)`` waveforms on the host (wav files,
    object stores, live hydrophone callbacks) and ships them to devices.

Host-fed sources additionally expose ``stream(plan, start, stop)`` — the
per-step payload iterator the engine actually drives.  The default
implementation fetches inline (the synchronous path);
:class:`PrefetchSource` overrides it to run the wrapped source through
:class:`repro.data.loader.SpeculativeLoader`, so reads for step k+depth
proceed on a host thread pool (with over-decomposition and speculative
re-execution of stragglers) while the devices compute step k.

Host-fed sources carry a **payload dtype**: ``"float32"`` (decoded
waveforms, the default) or ``"int16"`` (raw PCM transport — half the
host→device bytes, no host-side decode pass; the per-record float32
decode-scale sidecar from :meth:`Source.scales` rides along and the
Pallas kernels dequantize in VMEM, bitwise-identically).
``SoundscapeJob.payload("int16")`` flips it via :meth:`with_payload`;
:class:`PrefetchSource` transparently preserves whatever the wrapped
source ships.

``as_source`` normalizes what users pass to ``SoundscapeJob.source()``:
``None`` -> synthesis, a callable -> ``ReaderSource``, a path string ->
``WavSource``, a ``Source`` -> itself.
"""
from __future__ import annotations

import copy
from typing import Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.manifest import DatasetManifest, ShardPlan
from repro.core.params import DepamParams, PCM_DECODE_SCALE


def synth_record(idx: jnp.ndarray, m: DatasetManifest) -> jnp.ndarray:
    """Deterministic synthetic PAM record for a global record index.

    Colored-ish noise + a ship-like tonal + a burst of clicks, all keyed by
    the record index so regeneration is byte-exact (lineage property).
    idx: scalar int32 -> (record_size,) float32.
    """
    key = jax.random.fold_in(jax.random.PRNGKey(m.seed), idx)
    k1, k2, k3 = jax.random.split(key, 3)
    t = jnp.arange(m.record_size, dtype=jnp.float32) / m.fs
    noise = jax.random.normal(k1, (m.record_size,), jnp.float32)
    # crude red tilt: one-pole smoothing via cumsum decay approximation
    tone_f = 50.0 + 400.0 * jax.random.uniform(k2)
    tone = 0.3 * jnp.sin(2 * jnp.pi * tone_f * t)
    click_phase = jax.random.uniform(k3) * 0.9
    clicks = 2.0 * jnp.exp(-((t / t[-1] - click_phase) ** 2) * 4e5) \
        * jnp.sin(2 * jnp.pi * 9000.0 * t)
    return noise + tone + clicks


class Source:
    """Base class.  ``device_synth`` sources hand indices to the jitted
    step (which regenerates records on-device); host-fed sources
    implement ``fetch``."""

    device_synth: bool = False
    payload_dtype: str = "float32"

    def bind(self, m: DatasetManifest, p: DepamParams) -> "Source":
        """Late-bind the manifest/params at job start; returns self."""
        return self

    def with_payload(self, dtype: str) -> "Source":
        """Request a payload transport dtype (``"float32"``/``"int16"``).

        Sources that can ship raw PCM override this; the base accepts
        only the dtype the source already produces."""
        if dtype == self.payload_dtype:
            return self
        raise ValueError(
            f"{type(self).__name__} cannot ship {dtype!r} payloads "
            f"(it produces {self.payload_dtype!r}; device-synthesized "
            f"sources ship int32 indices and have no host payload)")

    def fetch(self, indices: np.ndarray) -> np.ndarray:
        """Global record indices -> waveforms of shape
        ``indices.shape + (record_size,)`` (zeros for padding slots), in
        ``payload_dtype`` (float32 decoded, or raw ``<i2`` PCM).

        The synchronous engine passes ``(n_shards, chunk)`` arrays, but
        implementations must NOT rely on that: the pipelined path
        (``PrefetchSource``) over-decomposes each step and calls
        ``fetch`` with flat 1-D sub-slices, concurrently from a thread
        pool.  Treat ``indices`` as an arbitrary-shaped batch of
        independent records — pure per index and thread-safe (the
        lineage property that also makes speculative duplicate reads
        and crash recomputation sound)."""
        raise NotImplementedError

    def scales(self, indices: np.ndarray) -> np.ndarray:
        """Per-record float32 decode-scale sidecar for int16 payloads:
        PCM full-scale x calibration gain, fused on the host (see
        ``data.wavio``).  Pure index arithmetic — no IO, ~4 bytes per
        record next to the 2-byte-per-sample payload.  The default is
        the plain full-scale factor (no calibration)."""
        return np.full(np.asarray(indices).shape, PCM_DECODE_SCALE,
                       np.float32)

    def stream(self, plan: ShardPlan, start: int, stop: int,
               rows: "slice | None" = None) -> Iterator[np.ndarray]:
        """Yield one payload per plan step in [start, stop), in order.

        The engine always consumes host-fed sources through this
        iterator; the base implementation is the synchronous path
        (fetch each step inline when the driver asks for it).

        ``rows`` restricts each step to a slice of the plan's leading
        shard axis — the ``jax.distributed`` seam: a process feeding a
        multi-host mesh streams only the shard rows its own devices
        hold, so no host ever reads (or assembles) another worker's
        files.  Single-process meshes leave it None and stream the full
        ``(n_shards, chunk)`` payload.
        """
        if rows is not None:
            plan = RowSlicePlan(plan, rows)
        for step in range(start, stop):
            yield self.fetch(plan.step_indices(step))

    def poll(self, indices: np.ndarray) -> str:
        """Non-blocking readiness probe for ``fetch(indices)``:
        ``"ready"`` (a fetch would return without blocking) or
        ``"pending"`` (data not yet available — a live stream still
        filling).  Batch/file sources are always ready; the scheduler
        uses this to skip starved live tenants instead of blocking the
        whole service on one tenant's ``fetch``."""
        return "ready"

    def stream_end(self) -> int | None:
        """One past the last record this source will ever deliver, or
        None for sources that cover the whole manifest (every batch
        source).  A finite value — a :class:`~repro.serve.LiveSource`
        whose feeder signalled end-of-stream — lets the engine mask out
        never-arriving records and finish the job gracefully."""
        return None

    def close(self) -> None:
        """Release IO resources (file handles, connections); called by
        the engine when the job finishes (or dies).  ``bind`` re-attaches
        them, so a closed source can run again.  Safe to call twice."""
        pass


class RowSlicePlan:
    """A view of a plan restricted to a slice of its shard rows.

    Duck-types the stepping surface (``n_steps`` / ``step_indices`` /
    ``step_mask``) that sources and the SpeculativeLoader drive, so one
    process of a multi-host job can prefetch exactly its own shards'
    records — its own files, under a file-aligned partition — while the
    step/commit geometry stays the global plan's.
    """

    def __init__(self, plan, rows: slice):
        self._plan = plan
        self._rows = rows

    def __getattr__(self, name):
        return getattr(self._plan, name)

    def step_indices(self, step: int) -> np.ndarray:
        return self._plan.step_indices(step)[self._rows]

    def step_mask(self, step: int) -> np.ndarray:
        return self._plan.step_mask(step)[self._rows]


class SynthSource(Source):
    """On-device synthesis from the manifest seed (no host IO)."""

    device_synth = True


class ReaderSource(Source):
    """Any host callback ``indices -> waveforms`` (e.g. WavRecordReader,
    a SpeculativeLoader-backed reader, or a live-stream shim).  The
    callback inherits :meth:`Source.fetch`'s contract: any index shape,
    pure per record, thread-safe under ``async_io``.

    ``payload_dtype="int16"`` declares that the callback returns raw
    ``<i2`` PCM; ``scales`` may then supply the per-record decode-scale
    sidecar (``indices -> float32``).  When the callback itself exposes
    ``scales_for`` (both wav readers in ``raw=True`` mode do), that is
    used automatically — a calibrated raw reader keeps its calibration
    without extra wiring.  The fallback is the plain full-scale decode.
    A float-returning callback on the int16 path is an error — silent
    requantization would corrupt the data, never do it implicitly.
    """

    def __init__(self, reader: Callable[[np.ndarray], np.ndarray],
                 payload_dtype: str = "float32",
                 scales: Callable[[np.ndarray], np.ndarray] | None = None):
        self.reader = reader
        self.payload_dtype = payload_dtype
        self._scales = scales

    def with_payload(self, dtype: str) -> "ReaderSource":
        if dtype == self.payload_dtype:
            return self
        if self.payload_dtype == "int16":
            # the callback itself produces raw PCM; unlike WavSource we
            # cannot re-bind it into decode mode, and casting PCM to
            # float32 without the decode scale would be silently 32767x
            # off — refuse instead
            raise ValueError(
                f"{type(self).__name__} wraps a raw-int16 reader and "
                f"cannot ship {dtype!r} payloads; wrap a decoding "
                f"reader instead (e.g. a raw=False wav reader)")
        new = copy.copy(self)
        new.payload_dtype = dtype
        return new

    def fetch(self, indices: np.ndarray) -> np.ndarray:
        out = np.asarray(self.reader(indices))
        want = np.int16 if self.payload_dtype == "int16" else np.float32
        if out.dtype == want:      # hot path: no conversion, no copy
            return out
        if want == np.int16:
            raise TypeError(
                f"reader returned {out.dtype} but the source ships raw "
                f"int16 PCM; requantizing floats would corrupt the data "
                f"— return '<i2' arrays (e.g. a raw=True wav reader)")
        if out.dtype == np.int16:
            raise TypeError(
                "reader returned raw int16 PCM on the float32 payload "
                "path; casting it would skip the decode scale (32767x "
                "amplitude error) — declare payload_dtype='int16' (or "
                ".payload('int16') on the job) to ship PCM, or have the "
                "reader decode to float32")
        return out.astype(np.float32)

    def scales(self, indices: np.ndarray) -> np.ndarray:
        if self._scales is not None:
            return np.asarray(self._scales(indices), np.float32)
        if hasattr(self.reader, "scales_for"):
            return np.asarray(self.reader.scales_for(indices), np.float32)
        return super().scales(indices)


class WavSource(Source):
    """Reads from a directory of wav files laid out by the manifest
    (uniform miniatures or a real heterogeneous corpus scanned by
    :func:`repro.data.wavio.scan_dataset`).

    By default reads go through the block-coalesced
    :class:`~repro.data.wavio.BlockReader` — indices grouped by file,
    contiguous runs merged into single reads, handles cached in a
    bounded LRU — which is bitwise-identical to the per-record path
    (``coalesced=False``, the debugging oracle).  ``calibration``
    applies a pypam-style per-file sensitivity gain; ``max_open_files``
    bounds the handle cache.

    ``payload_dtype="int16"`` (or ``.payload("int16")`` on the job)
    switches to raw-PCM transport: the readers return ``<i2`` straight
    from ``readframes`` — no host decode pass at all — and the
    calibration rides the :meth:`scales` sidecar instead of a
    full-array multiply.
    """

    def __init__(self, root: str, coalesced: bool = True,
                 max_open_files: int = 8, calibration=None,
                 payload_dtype: str = "float32"):
        self.root = root
        self.coalesced = coalesced
        self.max_open_files = max_open_files
        self.calibration = calibration
        self.payload_dtype = payload_dtype
        self._reader = None

    def with_payload(self, dtype: str) -> "WavSource":
        if dtype == self.payload_dtype:
            return self
        # copy, don't mutate: a source reused across jobs must not
        # inherit another job's transport setting
        new = copy.copy(self)
        new.payload_dtype = dtype
        new._reader = None          # bind() attaches the right-mode reader
        return new

    def bind(self, m: DatasetManifest, p: DepamParams) -> "WavSource":
        from repro.data.wavio import BlockReader, WavRecordReader
        raw = self.payload_dtype == "int16"
        if self.coalesced:
            self._reader = BlockReader(
                self.root, m, max_open_files=self.max_open_files,
                calibration=self.calibration, raw=raw)
        else:
            self._reader = WavRecordReader(
                self.root, m, calibration=self.calibration, raw=raw)
        return self

    def fetch(self, indices: np.ndarray) -> np.ndarray:
        assert self._reader is not None, "WavSource used before bind()"
        out = self._reader(indices)
        # readers already return the requested dtype — no copy
        return out if out.dtype == self._reader.dtype \
            else np.asarray(out, self._reader.dtype)

    def scales(self, indices: np.ndarray) -> np.ndarray:
        assert self._reader is not None, "WavSource used before bind()"
        return self._reader.scales_for(indices)

    def close(self) -> None:
        if self._reader is not None and hasattr(self._reader, "close"):
            self._reader.close()


class PrefetchSource(Source):
    """Drive any host-fed source through a :class:`SpeculativeLoader`.

    Wraps ``inner`` so that ``stream`` keeps ``depth`` plan steps of
    reads in flight on a host thread pool, each step over-decomposed
    into ``overdecompose`` read tasks with speculative re-execution of
    stragglers (first completion wins).  Because reads are pure
    functions of the record index (the lineage property), the streamed
    payloads are bitwise-identical to ``inner.fetch`` — prefetching
    changes *when* bytes arrive, never *what* arrives.

    ``SoundscapeJob.async_io(depth=...)`` applies this wrapper
    automatically; wrap explicitly to tune workers/over-decomposition
    or to reuse one wrapped source across jobs.
    """

    def __init__(self, inner: "Source | Callable | str", depth: int = 2,
                 workers: int = 4, overdecompose: int = 4,
                 speculate_factor: float = 4.0,
                 min_speculate_sec: float = 0.05):
        inner = as_source(inner)
        if inner.device_synth:
            raise ValueError(
                "PrefetchSource wraps host-fed sources; device-"
                "synthesized sources have no host IO to prefetch")
        self.inner = inner
        self.depth = max(1, depth)
        self.workers = workers
        self.overdecompose = overdecompose
        self.speculate_factor = speculate_factor
        self.min_speculate_sec = min_speculate_sec
        self._manifest: DatasetManifest | None = None

    @property
    def payload_dtype(self) -> str:
        """Prefetching never changes the bytes — the wrapped source's
        transport dtype (and its decode-scale sidecar) pass through."""
        return self.inner.payload_dtype

    def with_payload(self, dtype: str) -> "PrefetchSource":
        if dtype == self.payload_dtype:
            return self
        new = copy.copy(self)
        new.inner = self.inner.with_payload(dtype)
        return new

    def bind(self, m: DatasetManifest, p: DepamParams) -> "PrefetchSource":
        self.inner = self.inner.bind(m, p)
        self._manifest = m
        return self

    def fetch(self, indices: np.ndarray) -> np.ndarray:
        return self.inner.fetch(indices)

    def scales(self, indices: np.ndarray) -> np.ndarray:
        return self.inner.scales(indices)

    def poll(self, indices: np.ndarray) -> str:
        return self.inner.poll(indices)

    def stream_end(self) -> int | None:
        return self.inner.stream_end()

    def close(self) -> None:
        self.inner.close()

    def stream(self, plan: ShardPlan, start: int, stop: int,
               rows: "slice | None" = None) -> Iterator[np.ndarray]:
        from repro.data.loader import SpeculativeLoader
        if rows is not None:
            plan = RowSlicePlan(plan, rows)
        # read tasks split along the manifest's file boundaries (when
        # bound), so each task coalesces into sequential IO on one
        # file; a partitioned plan's span offsets join the cut set, so
        # no read task ever straddles two worker slices even when a cut
        # had to fall inside a file
        boundaries = None if self._manifest is None \
            else self._manifest.file_offsets
        offsets = getattr(plan, "offsets", None)
        if boundaries is not None and offsets is not None:
            boundaries = np.union1d(boundaries,
                                    np.asarray(offsets, np.int64))
        loader = SpeculativeLoader(
            self.inner.fetch, plan, workers=self.workers,
            overdecompose=self.overdecompose, depth=self.depth,
            speculate_factor=self.speculate_factor,
            min_speculate_sec=self.min_speculate_sec,
            boundaries=boundaries)
        try:
            for _step, payload, _mask in loader.iter_steps(start, stop):
                yield payload
        finally:
            loader.close()


def as_source(src) -> Source:
    """Normalize a user-supplied source (see module docstring)."""
    if src is None:
        return SynthSource()
    if isinstance(src, Source):
        return src
    if isinstance(src, str):
        return WavSource(src)
    if callable(src):
        return ReaderSource(src)
    raise TypeError(f"cannot interpret {type(src).__name__} as a Source")
