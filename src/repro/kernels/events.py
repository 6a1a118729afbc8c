"""Threshold + compaction kernel: frame SPL -> ragged event rows.

The detection workload PAM pipelines are actually run for (pypam's
``loud_event_detector`` / pile-driving analyses) produces a *variable*
number of events per record.  Devices cannot return ragged arrays, so
this kernel emits the standard count-prefixed fixed-capacity encoding:

  * ``counts``  — ``(batch,)`` int32, the TRUE number of qualifying
    events per record (NOT capped — ``counts > capacity`` is the
    per-record overflow flag, so capping is loud, never silent);
  * ``rows``    — ``(batch, capacity, 4)`` float32, the first
    ``min(count, capacity)`` events per record as
    ``(onset_frame, n_frames, peak_bin, peak_db)`` rows; unused slots
    are zero.

Detection semantics (a Schmitt trigger over the per-frame wideband SPL):
a frame OPENS an event when ``spl >= threshold_db`` and no event is
open; an open event CLOSES at the first frame with
``spl < threshold_db - hysteresis_db`` (duration excludes that frame) or
at the record end (events touching the record edge close there — they
are reported, not dropped).  Events shorter than ``min_len`` frames are
discarded.  ``peak_db`` is the maximum frame SPL inside the event (first
frame wins ties) and ``peak_bin`` is that frame's argmax PSD bin.

One scan body (:func:`scan_events`, pure jnp — comparisons, selects and
integer adds only, no rounding anywhere) is shared verbatim by the
Pallas kernel and the XLA fallback, which differ only in how a frame is
read, so the two paths are bitwise-equal
by construction; ``tests/test_events.py`` additionally pins both to a
NumPy oracle under hypothesis.  The kernel runs the scan per batch block
in VMEM (grid over records) so the event stream compacts on-device —
only counts + capacity rows ever cross back to the host, not the
``(batch, n_frames)`` SPL trace.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import common

N_EVENT_COLS = 4          # onset_frame, n_frames, peak_bin, peak_db
LANES = 128               # frames per VMEM tile row in the Pallas kernel


def scan_events(read, *, b: int, f_total: int, n_frames: int,
                threshold_db: float, hysteresis_db: float, min_len: int,
                capacity: int) -> tuple[jnp.ndarray, tuple[jnp.ndarray, ...]]:
    """The shared scan body over ``f_total`` frames of ``b`` records.

    ``read(f)`` returns frame ``f``'s SPL (float32) and peak bin
    (int32), each ``(b, 1)``: the XLA fallback slices its (B, F) arrays,
    the Pallas body loads the frame from its VMEM block.  Returns
    ``(counts (b, 1) int32, cols)`` with ``cols`` the four ``(b,
    capacity)`` float32 row columns (onset, duration, peak bin, peak
    dB); every state array is 2-D with records on the sublane axis, the
    layout the TPU lowering accepts.

    The SPL may carry padding frames beyond ``n_frames`` as long as they
    are ``-inf`` (strictly below any finite close level): a pad frame
    then closes a still-open event with the exact same duration the
    record-end closure below produces, and can never open one — the
    padded and unpadded scans agree bitwise.
    """
    k = capacity
    thr = jnp.float32(threshold_db)
    lo = jnp.float32(threshold_db) - jnp.float32(hysteresis_db)
    slots = jax.lax.broadcasted_iota(jnp.int32, (b, k), 1)

    def emit(count, cols, qualify, start, dur, pk_bin, pk_db):
        """Append one closing event per record where ``qualify``."""
        hot = qualify & (slots == count)                  # count < K only
        vals = (start.astype(jnp.float32), dur.astype(jnp.float32),
                pk_bin.astype(jnp.float32), pk_db)
        cols = tuple(jnp.where(hot, v, c) for v, c in zip(vals, cols))
        return count + qualify.astype(jnp.int32), cols

    def body(f, st):
        open_, start, pk_db, pk_bin, count, cols = st
        in_ev = open_ != 0
        s, pb = read(f)
        # close: first frame below the hysteresis level ends the event
        closing = in_ev & (s < lo)
        dur = f - start
        count, cols = emit(count, cols, closing & (dur >= min_len),
                           start, dur, pk_bin, pk_db)
        in_ev = in_ev & ~closing
        # continue: track the peak frame (strict >, first frame wins ties)
        better = in_ev & (s > pk_db)
        pk_db = jnp.where(better, s, pk_db)
        pk_bin = jnp.where(better, pb, pk_bin)
        # open: s < lo <= threshold on a closing frame, so no re-trigger
        opening = ~in_ev & (s >= thr)
        start = jnp.where(opening, f, start)
        pk_db = jnp.where(opening, s, pk_db)
        pk_bin = jnp.where(opening, pb, pk_bin)
        return ((in_ev | opening).astype(jnp.int32), start, pk_db, pk_bin,
                count, cols)

    # the open flag is carried as int32: Mosaic cannot carry an i1
    # vector through a loop
    one = (b, 1)
    init = (jnp.zeros(one, jnp.int32),                      # event open
            jnp.zeros(one, jnp.int32),                      # start frame
            jnp.full(one, -jnp.inf, jnp.float32),           # peak SPL
            jnp.zeros(one, jnp.int32),                      # peak bin
            jnp.zeros(one, jnp.int32),                      # count
            tuple(jnp.zeros((b, k), jnp.float32)
                  for _ in range(N_EVENT_COLS)))            # row columns
    open_, start, pk_db, pk_bin, count, cols = jax.lax.fori_loop(
        0, f_total, body, init)
    # events still open at the TRUE record end close there
    dur = jnp.int32(n_frames) - start
    return emit(count, cols, (open_ != 0) & (dur >= min_len),
                start, dur, pk_bin, pk_db)


@functools.partial(jax.jit, static_argnames=(
    "threshold_db", "hysteresis_db", "min_len", "capacity"))
def detect_events_xla(spl: jnp.ndarray, peak_bin: jnp.ndarray, *,
                      threshold_db: float, hysteresis_db: float,
                      min_len: int, capacity: int):
    """XLA fallback (reference form, kernels/ref.py discipline): the
    scan body jitted directly, no padding, no grid."""
    b, n_frames = spl.shape

    def read(f):
        return (jax.lax.dynamic_slice_in_dim(spl, f, 1, axis=1),
                jax.lax.dynamic_slice_in_dim(peak_bin, f, 1, axis=1))

    count, cols = scan_events(
        read, b=b, f_total=n_frames, n_frames=n_frames,
        threshold_db=threshold_db, hysteresis_db=hysteresis_db,
        min_len=min_len, capacity=capacity)
    return count[:, 0], jnp.stack(cols, axis=-1)


def _events_body(spl_ref, pbin_ref, cnt_ref, rows_ref, *, n_frames,
                 threshold_db, hysteresis_db, min_len, capacity):
    """One record block.  The refs hold (n_tiles, block, LANES) tiles:
    frame ``f`` of every record sits in lane ``f % LANES`` of tile
    ``f // LANES``.  A frame is read as a dynamic tile load plus a
    one-lane select-and-max — exact, since every other lane is the
    reduction's identity — because the TPU lowering has no dynamic
    slice along lanes."""
    n_tiles, b, _ = spl_ref.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (b, LANES), 1)

    def read(f):
        hit = lane == f % LANES
        t = f // LANES
        s = jnp.max(jnp.where(hit, spl_ref[t], -jnp.inf), axis=1,
                    keepdims=True)
        pb = jnp.max(jnp.where(hit, pbin_ref[t], jnp.iinfo(jnp.int32).min),
                     axis=1, keepdims=True)
        return s, pb

    count, cols = scan_events(
        read, b=b, f_total=n_tiles * LANES, n_frames=n_frames,
        threshold_db=threshold_db, hysteresis_db=hysteresis_db,
        min_len=min_len, capacity=capacity)
    cnt_ref[...] = count
    for c, col in enumerate(cols):
        rows_ref[c] = col


@functools.partial(jax.jit, static_argnames=(
    "threshold_db", "hysteresis_db", "min_len", "capacity",
    "block_records", "interpret"))
def detect_events(spl: jnp.ndarray, peak_bin: jnp.ndarray, *,
                  threshold_db: float, hysteresis_db: float,
                  min_len: int = 1, capacity: int = 16,
                  block_records: int = 8,
                  interpret: bool | None = None):
    """Pallas threshold+compaction: (B, F) f32 SPL + int32 peak bins ->
    ``(counts (B,) int32, rows (B, capacity, 4) f32)``.

    Grid over record blocks; each block scans its SPL trace in VMEM and
    writes only the compacted encoding back.  Frame padding uses
    ``-inf`` (see :func:`scan_events`), record padding scans garbage
    rows that are sliced off before returning.
    """
    if interpret is None:
        interpret = common.use_interpret()
    assert spl.ndim == 2 and spl.shape == peak_bin.shape
    n_rec, n_frames = spl.shape
    block_records = min(block_records, max(n_rec, 1))
    bpad = common.round_up(max(n_rec, 1), block_records)
    # frames padded to the lane width with -inf: closes edge events at
    # the true record end, never opens one
    fpad = common.round_up(n_frames, LANES)
    spl = jnp.pad(spl.astype(jnp.float32),
                  ((0, bpad - n_rec), (0, fpad - n_frames)),
                  constant_values=-jnp.inf)
    peak_bin = jnp.pad(peak_bin.astype(jnp.int32),
                       ((0, bpad - n_rec), (0, fpad - n_frames)))

    def tiles(x):          # (bpad, fpad) -> (fpad // LANES, bpad, LANES)
        return x.reshape(bpad, fpad // LANES, LANES).transpose(1, 0, 2)

    n_tiles = fpad // LANES
    body = functools.partial(
        _events_body, n_frames=n_frames, threshold_db=threshold_db,
        hysteresis_db=hysteresis_db, min_len=min_len, capacity=capacity)
    counts, rows = pl.pallas_call(
        body,
        grid=(bpad // block_records,),
        in_specs=[
            pl.BlockSpec((n_tiles, block_records, LANES),
                         lambda i: (0, i, 0)),
            pl.BlockSpec((n_tiles, block_records, LANES),
                         lambda i: (0, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_records, 1), lambda i: (i, 0)),
            pl.BlockSpec((N_EVENT_COLS, block_records, capacity),
                         lambda i: (0, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bpad, 1), jnp.int32),
            jax.ShapeDtypeStruct((N_EVENT_COLS, bpad, capacity),
                                 jnp.float32),
        ],
        interpret=interpret,
        name="detect_events",
    )(tiles(spl), tiles(peak_bin))
    return counts[:n_rec, 0], jnp.moveaxis(rows, 0, -1)[:n_rec]
