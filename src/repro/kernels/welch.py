"""Welch reduction kernel: mean over the frame axis of per-frame PSDs.

Used when the per-frame PSD was materialized anyway (LTSA-fine products);
the fused path in framepsd.welch_psd avoids materializing it at all.

Grid (record_blocks, bin_blocks, frame_chunks); frame chunks are the
innermost (sequential) axis and accumulate into the output block, so the
output block is revisited — the canonical Pallas reduction pattern.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import common


def _body(x_ref, o_ref, *, inv_n: float):
    f = pl.program_id(2)

    @pl.when(f == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.sum(x_ref[...], axis=1) * inv_n


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def welch_mean(frame_psd: jnp.ndarray, block_records: int = 8,
               block_bins: int = 128, chunk_frames: int = 256,
               interpret: bool | None = None) -> jnp.ndarray:
    """(n_records, n_frames, n_bins) -> (n_records, n_bins) mean."""
    if interpret is None:
        interpret = common.use_interpret()
    n_rec, n_frames, n_bins = frame_psd.shape
    chunk_frames = min(chunk_frames, common.round_up(n_frames, 8))

    rpad = common.round_up(n_rec, block_records)
    fpad = common.round_up(n_frames, chunk_frames)
    bpad = common.round_up(n_bins, block_bins)
    x = common.pad_axis(frame_psd, 0, rpad)
    x = common.pad_axis(x, 1, fpad)          # zero frames add 0 to the sum
    x = common.pad_axis(x, 2, bpad)

    grid = (rpad // block_records, bpad // block_bins, fpad // chunk_frames)
    out = pl.pallas_call(
        functools.partial(_body, inv_n=1.0 / n_frames),
        grid=grid,
        in_specs=[pl.BlockSpec((block_records, chunk_frames, block_bins),
                               lambda r, k, f: (r, f, k))],
        out_specs=pl.BlockSpec((block_records, block_bins),
                               lambda r, k, f: (r, k)),
        out_shape=jax.ShapeDtypeStruct((rpad, bpad), jnp.float32),
        interpret=interpret,
        name="welch_mean",
    )(x.astype(jnp.float32))
    return out[:n_rec, :n_bins]
