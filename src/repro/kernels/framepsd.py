"""Fused frame + window + real-DFT + power kernel (direct matmul form).

TPU-native replacement for the CPU radix FFT in the paper's Scala/Spark
chain: for the small analysis windows used by DEPAM (paper set 1:
nfft = windowSize = 256, hop 128) a *direct* real-DFT as a matmul is
MXU-shaped and fuses the whole per-frame chain —

    frames -> window -> rfft -> |.|^2 -> density scale

— into one pallas_call, so neither the frame matrix nor the complex
spectrum ever round-trips through HBM.

Frame extraction trick (requires hop | window_size, true for both paper
parameter sets): with m = window_size/hop and H = reshape(x, (n_hops, hop)),
frame i is rows i..i+m-1 of H.  Pass the m shifted views V_r = H[r:r+nf]
(stacked, shape (m, nf, hop)) and fold the analysis window into the DFT
matrices:

    rfft(w * frame_i)[k] = sum_r V_r[i] @ Cw_r[:, k]  (+ i * ... Sw_r)

so the kernel is m matmul-accumulates followed by a squared-magnitude and
per-bin scale.  All matmul dims (hop, n_bins blocks) are chosen
128-aligned for the MXU.

Two variants:
  * ``frame_psd_kernel``  — per-frame PSD (the LTSA-fine product),
    grid (frame_blocks, bin_blocks).
  * ``welch_psd_kernel``  — per-record Welch PSD with in-kernel frame
    accumulation, grid (records, bin_blocks, frame_chunks); the per-frame
    PSD never exists in HBM.  This is the beyond-paper fused variant
    measured in EXPERIMENTS.md §Perf.

Both accept **raw int16 PCM** payloads (dtype drives the dispatch): the
hop-views stay int16 all the way into VMEM, and the kernel body
dequantizes each block with one convert + one multiply by the per-record
decode scale (the sidecar from ``data.wavio``, PCM full-scale x
calibration fused on host) right before the DFT matmuls.  The float32
waveform therefore never exists in HBM, host→device payload traffic is
halved, and — because it is the exact same single f32 rounding the host
decode performs — the results are bitwise-identical to the float path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import common

_PREC = jax.lax.Precision.HIGHEST


def _views(x: jnp.ndarray, window_size: int, hop: int) -> jnp.ndarray:
    """(..., n_samples) -> (m, ..., n_frames, hop) shifted hop-views."""
    assert window_size % hop == 0, "fused kernel requires hop | window_size"
    m = window_size // hop
    n = x.shape[-1]
    n_frames = (n - window_size) // hop + 1
    n_hops = n // hop
    h = x[..., : n_hops * hop].reshape(*x.shape[:-1], n_hops, hop)
    return jnp.stack([h[..., r : r + n_frames, :] for r in range(m)], axis=0)


def _fold_matrices(p, dtype=np.float32):
    """Split window-folded DFT matrices by hop phase: (m, hop, n_bins)."""
    from repro.core.windows import np_window

    w = np_window(p.window, p.window_size)
    c, s = common.dft_matrices(p.window_size, p.nfft, w, dtype=np.float64)
    m = p.window_size // p.hop
    c = c.reshape(m, p.hop, p.n_bins).astype(dtype)
    s = s.reshape(m, p.hop, p.n_bins).astype(dtype)
    return c, s


def _bin_scale(p, extra: float = 1.0, dtype=np.float32) -> np.ndarray:
    """Combined one-sided weight * density scale (* extra), (1, n_bins)."""
    from repro.core.spectra import np_onesided_weights, periodogram_scale

    w = np_onesided_weights(p.nfft)
    return (w * periodogram_scale(p) * extra).astype(dtype)[None, :]


def _dft_accum(view, c_ref, s_ref, *, m: int):
    """Accumulate the m hop-phase matmuls: sum_r view(r) @ (C_r, S_r).

    ``view(r)`` yields the (rows, hop) float32 block for phase r — the
    raw VMEM block on the float path, or the dequantized block (one
    convert + one traced scale multiply, the host decode's exact
    rounding) on the int16 path.  Shared by all four kernel bodies so
    the two transports can never drift apart.
    """
    acc_r = None
    acc_i = None
    for r in range(m):  # static unroll over hop phases
        v = view(r)
        cr = jnp.dot(v, c_ref[r], precision=_PREC,
                     preferred_element_type=jnp.float32)
        ci = jnp.dot(v, s_ref[r], precision=_PREC,
                     preferred_element_type=jnp.float32)
        acc_r = cr if acc_r is None else acc_r + cr
        acc_i = ci if acc_i is None else acc_i + ci
    return acc_r, acc_i


# ----------------------------------------------------------------------
# Variant 1: per-frame PSD
# ----------------------------------------------------------------------

def _frame_psd_body(v_ref, c_ref, s_ref, scale_ref, o_ref, *, m: int):
    acc_r, acc_i = _dft_accum(lambda r: v_ref[r], c_ref, s_ref, m=m)
    o_ref[...] = (acc_r * acc_r + acc_i * acc_i) * scale_ref[0, :]


def _frame_psd_body_q(v_ref, q_ref, c_ref, s_ref, scale_ref, o_ref,
                      *, m: int):
    """int16 variant: ``q_ref`` holds the per-frame decode scale
    (block_frames, 1), applied to the samples BEFORE the DFT matmul —
    the same order as the host decode, so results match bitwise."""
    q = q_ref[...]
    acc_r, acc_i = _dft_accum(
        lambda r: v_ref[r].astype(jnp.float32) * q, c_ref, s_ref, m=m)
    o_ref[...] = (acc_r * acc_r + acc_i * acc_i) * scale_ref[0, :]


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def frame_psd(x: jnp.ndarray, p, block_frames: int = 256,
              block_bins: int = 128, interpret: bool | None = None,
              scales: jnp.ndarray | None = None) -> jnp.ndarray:
    """Per-frame one-sided PSD via the fused Pallas kernel.

    x: (n_samples,) or (n_records, record_size), float32 OR raw int16
    PCM (then ``scales`` carries the per-record decode scales — one per
    record for batched input, a scalar for 1-D input; None = plain
    full-scale decode).
    returns (n_frames, n_bins) or (n_records, frames_per_record, n_bins).
    """
    if interpret is None:
        interpret = common.use_interpret()
    quantized = x.dtype == jnp.int16
    batched = x.ndim == 2
    v = _views(x if quantized else x.astype(jnp.float32),
               p.window_size, p.hop)                     # (m,[R,]nf,hop)
    m = v.shape[0]
    nf = v.shape[-2]
    if batched:
        n_rec = x.shape[0]
        v = v.reshape(m, n_rec * nf, p.hop)
    total_frames = v.shape[1]

    c, s = _fold_matrices(p)
    scale = _bin_scale(p)

    fpad = common.round_up(total_frames, block_frames)
    bpad = common.round_up(p.n_bins, block_bins)
    v = common.pad_axis(v, 1, fpad)
    c = np.pad(c, ((0, 0), (0, 0), (0, bpad - p.n_bins)))
    s = np.pad(s, ((0, 0), (0, 0), (0, bpad - p.n_bins)))
    scale = np.pad(scale, ((0, 0), (0, bpad - p.n_bins)))

    grid = (fpad // block_frames, bpad // block_bins)
    in_specs = [
        pl.BlockSpec((m, block_frames, p.hop), lambda i, k: (0, i, 0)),
        pl.BlockSpec((m, p.hop, block_bins), lambda i, k: (0, 0, k)),
        pl.BlockSpec((m, p.hop, block_bins), lambda i, k: (0, 0, k)),
        pl.BlockSpec((1, block_bins), lambda i, k: (0, k)),
    ]
    operands = [v, jnp.asarray(c), jnp.asarray(s), jnp.asarray(scale)]
    body = functools.partial(_frame_psd_body, m=m)
    if quantized:
        # per-record decode scales -> one scale per (flattened) frame
        if scales is None:
            sf = jnp.full((total_frames,), common.PCM_DECODE_SCALE,
                          jnp.float32)
        elif batched:
            sf = jnp.broadcast_to(
                jnp.asarray(scales, jnp.float32)[:, None],
                (n_rec, nf)).reshape(-1)
        else:
            sf = jnp.full((total_frames,),
                          jnp.asarray(scales, jnp.float32))
        sf = common.pad_axis(sf, 0, fpad).reshape(fpad, 1)
        in_specs.insert(1, pl.BlockSpec((block_frames, 1),
                                        lambda i, k: (i, 0)))
        operands.insert(1, sf)
        body = functools.partial(_frame_psd_body_q, m=m)

    out = pl.pallas_call(
        body,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_frames, block_bins),
                               lambda i, k: (i, k)),
        out_shape=jax.ShapeDtypeStruct((fpad, bpad), jnp.float32),
        interpret=interpret,
        name="frame_psd",
    )(*operands)

    out = out[:total_frames, : p.n_bins]
    if batched:
        out = out.reshape(n_rec, nf, p.n_bins)
    return out


# ----------------------------------------------------------------------
# Variant 2: fused Welch (per-record mean PSD, frames never materialized)
# ----------------------------------------------------------------------

def _welch_update(view, c_ref, s_ref, scale_ref, o_ref, *, m: int):
    """One frame-chunk's contribution to the per-record Welch mean.

    ``o_ref`` is the record's (1, block_bins) row of the (n_rec, 1,
    bpad) output: a singleton middle axis, so the block spans whole
    trailing axes as the TPU lowering requires."""
    f = pl.program_id(2)

    @pl.when(f == 0)
    def _init():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

    acc_r, acc_i = _dft_accum(view, c_ref, s_ref, m=m)
    psd = acc_r * acc_r + acc_i * acc_i            # (chunk_frames, bins)
    o_ref[0] += jnp.sum(psd, axis=0, keepdims=True) * scale_ref[0, :]


def _welch_body(v_ref, c_ref, s_ref, scale_ref, o_ref, *, m: int):
    _welch_update(lambda r: v_ref[r, 0], c_ref, s_ref, scale_ref, o_ref,
                  m=m)


def _welch_body_q(v_ref, q_ref, c_ref, s_ref, scale_ref, o_ref, *, m: int):
    """int16 variant: one decode scale per record (``q_ref`` (1, 1, 1)),
    applied to the samples before the matmul chain — same rounding
    order as the host decode, so the fused Welch stays bitwise-equal."""
    q = q_ref[0, 0, 0]
    _welch_update(lambda r: v_ref[r, 0].astype(jnp.float32) * q,
                  c_ref, s_ref, scale_ref, o_ref, m=m)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def welch_psd(records: jnp.ndarray, p, chunk_frames: int = 512,
              block_bins: int = 128, interpret: bool | None = None,
              scales: jnp.ndarray | None = None) -> jnp.ndarray:
    """Per-record Welch PSD, (n_records, record_size) -> (n_records, n_bins).

    The frame axis is reduced inside the kernel (grid axis 2, innermost) so
    per-frame spectra never hit HBM — HBM traffic is m * signal + output.
    ``records`` may be raw int16 PCM (``scales``: per-record decode
    scales, (n_records,); None = plain full-scale decode); the float32
    waveform then never exists in HBM either.
    """
    if interpret is None:
        interpret = common.use_interpret()
    assert records.ndim == 2
    quantized = records.dtype == jnp.int16
    n_rec = records.shape[0]
    v = _views(records if quantized else records.astype(jnp.float32),
               p.window_size, p.hop)
    m, _, fpr, hop = v.shape

    c, s = _fold_matrices(p)
    scale = _bin_scale(p, extra=1.0 / fpr)  # fold the Welch mean in

    chunk_frames = min(chunk_frames, common.round_up(fpr, 8))
    fpad = common.round_up(fpr, chunk_frames)
    bpad = common.round_up(p.n_bins, block_bins)
    v = common.pad_axis(v, 2, fpad)
    c = np.pad(c, ((0, 0), (0, 0), (0, bpad - p.n_bins)))
    s = np.pad(s, ((0, 0), (0, 0), (0, bpad - p.n_bins)))
    scale = np.pad(scale, ((0, 0), (0, bpad - p.n_bins)))

    grid = (n_rec, bpad // block_bins, fpad // chunk_frames)
    in_specs = [
        pl.BlockSpec((m, 1, chunk_frames, hop),
                     lambda r, k, f: (0, r, f, 0)),
        pl.BlockSpec((m, hop, block_bins), lambda r, k, f: (0, 0, k)),
        pl.BlockSpec((m, hop, block_bins), lambda r, k, f: (0, 0, k)),
        pl.BlockSpec((1, block_bins), lambda r, k, f: (0, k)),
    ]
    operands = [v, jnp.asarray(c), jnp.asarray(s), jnp.asarray(scale)]
    body = functools.partial(_welch_body, m=m)
    if quantized:
        if scales is None:
            sq = jnp.full((n_rec, 1, 1), common.PCM_DECODE_SCALE,
                          jnp.float32)
        else:
            sq = jnp.asarray(scales, jnp.float32).reshape(n_rec, 1, 1)
        in_specs.insert(1, pl.BlockSpec((1, 1, 1),
                                        lambda r, k, f: (r, 0, 0)))
        operands.insert(1, sq)
        body = functools.partial(_welch_body_q, m=m)

    # per-record blocks carry a singleton middle axis: a (1, block)
    # block of an (n_rec, bpad) array is refused by the TPU lowering
    # (second-minor block dim neither a multiple of 8 nor the whole axis)
    out = pl.pallas_call(
        body,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, block_bins),
                               lambda r, k, f: (r, 0, k)),
        out_shape=jax.ShapeDtypeStruct((n_rec, 1, bpad), jnp.float32),
        interpret=interpret,
        name="welch_psd",
    )(*operands)

    return out[:, 0, : p.n_bins]
