"""Exact order statistics of spectrogram columns, without a sort.

The spectrum percentiles read 14 order statistics of each (record,
frequency bin) column of the dB spectrogram: the low and high taps of
np.percentile's linear interpolation at 1, 5, 25, 50, 75, 95 and 99%.
Sorting the 15,359 frames of a paper-set-1 record to read them costs
most of the step on the chip; selecting them costs a fixed number of
counting passes instead.

Each float32 becomes an int32 key whose signed order is the float
order (:func:`order_keys`, with -0.0 taken as +0.0 as ``lax.sort``'s
comparator takes it).  The rank-``lo`` key is then found bit by bit
from the top: a candidate prefix stays when at most ``lo`` keys lie
below it, 32 counting passes for all ranks at once.  The next rank's
key is that key again when more than ``lo + 1`` keys are at or below
it, else the least key above it: one more pass.  The result is the
element a sort would put there, so the output is bitwise the sort's.

The Pallas kernel holds one (record, 8 bins) tile of keys, frames on
lanes, in VMEM across all 33 passes, so HBM is read once; the counts
accumulate elementwise over 256-frame chunks and are reduced across
lanes once a pass.  Records stay independent, so a batch sharded over
a mesh is selected where it lies.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import common

ROWS = 8         # frequency bins per block, on sublanes
CHUNK = 256      # frames per inner step, on lanes
UNROLL = 4       # chunks per iteration of the inner loop
KEY_MIN = np.iinfo(np.int32).min
KEY_MAX = np.iinfo(np.int32).max


def order_keys(x: jnp.ndarray) -> jnp.ndarray:
    """int32 keys whose signed order is the float32 order of ``x``, with
    -0.0 taken as +0.0: negative values flip every bit but the sign."""
    bits = lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.int32)
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def key_values(keys: jnp.ndarray) -> jnp.ndarray:
    """The float32 values of :func:`order_keys`' keys."""
    return lax.bitcast_convert_type(keys ^ ((keys >> 31) & 0x7FFFFFFF),
                                    jnp.float32)


def _body(x_ref, o_ref, *, lo: tuple[int, ...], hi: tuple[int, ...],
          n_chunks: int):
    """One (1, ROWS, frames) tile of keys -> (1, ROWS, 128) int32 whose
    lanes 2j and 2j + 1 hold the keys at ranks lo[j] and hi[j]."""
    t = len(lo)
    shape = (ROWS, CHUNK)
    unroll = max(u for u in range(1, UNROLL + 1) if n_chunks % u == 0)

    def sweep(update, init):
        def chunks(c, acc):
            for u in range(unroll):
                start = pl.multiple_of((c * unroll + u) * CHUNK, CHUNK)
                acc = update(x_ref[0, :, pl.ds(start, CHUNK)], acc)
            return acc
        return lax.fori_loop(0, n_chunks // unroll, chunks, init)

    def bit(i, ans):
        m = lax.shift_left(jnp.int32(1), 31 - i)
        cand = [jnp.broadcast_to(a ^ m, shape) for a in ans]
        below = sweep(lambda x, acc: tuple(
            n + (x < c).astype(jnp.int32) for n, c in zip(acc, cand)),
            tuple(jnp.zeros(shape, jnp.int32) for _ in range(t)))
        return tuple(jnp.where(jnp.sum(n, axis=1, keepdims=True) <= r,
                               c[:, :1], a)
                     for n, c, a, r in zip(below, cand, ans, lo))

    k_lo = lax.fori_loop(0, 32, bit, tuple(
        jnp.full((ROWS, 1), KEY_MIN, jnp.int32) for _ in range(t)))
    k = [jnp.broadcast_to(a, shape) for a in k_lo]

    def at_or_below_and_above(x, acc):
        n, m = acc
        return (tuple(a + (x <= b).astype(jnp.int32) for a, b in zip(n, k)),
                tuple(jnp.minimum(a, jnp.where(x > b, x, KEY_MAX))
                      for a, b in zip(m, k)))
    n, above = sweep(at_or_below_and_above, (
        tuple(jnp.zeros(shape, jnp.int32) for _ in range(t)),
        tuple(jnp.full(shape, KEY_MAX, jnp.int32) for _ in range(t))))

    lane = lax.broadcasted_iota(jnp.int32, (ROWS, 128), 1)
    out = jnp.zeros((ROWS, 128), jnp.int32)
    for j in range(t):
        k_hi = k_lo[j] if hi[j] == lo[j] else jnp.where(
            jnp.sum(n[j], axis=1, keepdims=True) > lo[j] + 1, k_lo[j],
            jnp.min(above[j], axis=1, keepdims=True))
        out = jnp.where(lane == 2 * j, k_lo[j], out)
        out = jnp.where(lane == 2 * j + 1, k_hi, out)
    o_ref[0] = out


@functools.partial(jax.jit, static_argnames=("lo", "hi"))
def select_order_stats(db: jnp.ndarray, lo: tuple[int, ...],
                       hi: tuple[int, ...]):
    """(batch, n_frames, n_bins) float32 -> the values at ranks ``lo`` and
    ``hi`` of each column sorted over frames, each (batch, len(lo),
    n_bins): ``jnp.sort(db, axis=1)[:, lo]`` and ``[:, hi]`` bit for bit
    where a column holds no NaN.  ``hi[j]`` is ``lo[j] + 1``, or
    ``lo[j]`` at the last rank."""
    b, f, n = db.shape
    assert 2 * len(lo) <= 128 and all(h in (r, r + 1) for r, h in
                                      zip(lo, hi)), (lo, hi)
    fpad = common.round_up(f, CHUNK)
    npad = common.round_up(n, ROWS)
    # frames on lanes; pad frames with the largest key, which no
    # candidate counts below it
    keys = jnp.pad(jnp.swapaxes(order_keys(db), 1, 2),
                   ((0, 0), (0, npad - n), (0, fpad - f)),
                   constant_values=KEY_MAX)
    out = pl.pallas_call(
        functools.partial(_body, lo=lo, hi=hi, n_chunks=fpad // CHUNK),
        grid=(b, npad // ROWS),
        in_specs=[pl.BlockSpec((1, ROWS, fpad), lambda r, k: (r, k, 0))],
        out_specs=pl.BlockSpec((1, ROWS, 128), lambda r, k: (r, k, 0)),
        out_shape=jax.ShapeDtypeStruct((b, npad, 128), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=common.use_interpret(),
        name="select_order_stats",
    )(keys)
    out = out[:, :n, :2 * len(lo)].reshape(b, n, len(lo), 2)
    return tuple(key_values(jnp.swapaxes(out[..., i], 1, 2)) for i in (0, 1))
