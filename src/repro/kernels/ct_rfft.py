"""Radix-(N1 x N2) Cooley-Tukey power-spectrum kernel for large nfft.

For paper parameter set 2 (nfft = windowSize = 4096, no overlap) a direct
DFT matmul does 4*N*(N/2+1) ~ 33.6 MFLOP/frame.  Factorizing N = N1*N2
(4096 = 64*64) as two matmul stages + twiddle does ~2.2 MFLOP/frame — a
15x FLOP cut that STAYS matmul-shaped for the MXU, which is the TPU-native
answer to the paper's CPU radix FFT (butterflies do not vectorize on the
MXU at all; this does).

Derivation (n = N2*n1 + n2, k = k1 + N1*k2):

    A[n1, n2]   = (w * x)[N2*n1 + n2]            -- row-major reshape, no transpose
    Y[k1, n2]   = sum_n1 A[n1, n2] W_N1^(n1 k1)   -- stage 1: D1 @ A   (D1 symmetric)
    Z[k1, n2]   = Y[k1, n2] * W_N^(k1 n2)         -- twiddle
    X[k1+N1*k2] = sum_n2 Z[k1, n2] W_N2^(n2 k2)   -- stage 2: Z @ D2

Real input => stage 1 is two real matmuls; one-sided output => stage 2 only
needs k2 in [0, N2/2], i.e. D2 restricted to N2/2+1 columns.  The power
|X|^2 lands as a (N1, N2/2+1) matrix whose (k2, k1) row-major flatten is the
bin index k; the kernel writes it transposed with the density scale folded
in, and the wrapper slices bins [0, nfft/2].

Grid: 1-D over frame blocks; all DFT/twiddle constants live in VMEM
(< 200 KB total for 4096).  VMEM high-water at block_frames=32 is ~4.5 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import common

_PREC = jax.lax.Precision.HIGHEST


def _constants(p, n1: int, n2: int, dtype=np.float32):
    from repro.core.spectra import np_onesided_weights, periodogram_scale
    from repro.core.windows import np_window

    nfft = p.nfft
    assert n1 * n2 == nfft
    n2h = n2 // 2 + 1

    w = np_window(p.window, p.window_size)
    w = np.pad(w, (0, nfft - p.window_size))  # zero-padded FFT case
    wmat = w.reshape(n1, n2)

    j1 = np.arange(n1)[:, None].astype(np.float64)
    k1 = np.arange(n1)[None, :].astype(np.float64)
    ang1 = 2.0 * np.pi * j1 * k1 / n1
    c1, s1 = np.cos(ang1), -np.sin(ang1)

    kk1 = np.arange(n1)[:, None].astype(np.float64)
    nn2 = np.arange(n2)[None, :].astype(np.float64)
    angt = 2.0 * np.pi * kk1 * nn2 / nfft
    tr, ti = np.cos(angt), -np.sin(angt)

    j2 = np.arange(n2)[:, None].astype(np.float64)
    k2 = np.arange(n2h)[None, :].astype(np.float64)
    ang2 = 2.0 * np.pi * j2 * k2 / n2
    c2, s2 = np.cos(ang2), -np.sin(ang2)

    # Per-bin scale laid out as the kernel's (n2h, n1) output: bin k1+n1*k2.
    ow = np_onesided_weights(nfft)
    scale_flat = np.zeros(n2h * n1)
    scale_flat[: nfft // 2 + 1] = ow * periodogram_scale(p)
    scale = scale_flat.reshape(n2h, n1)

    return [a.astype(dtype) for a in (wmat, c1, s1, tr, ti, c2, s2, scale)]


def _body(x_ref, w_ref, c1_ref, s1_ref, tr_ref, ti_ref, c2_ref, s2_ref,
          sc_ref, o_ref, *, n1: int, n2: int):
    _chain(x_ref[...], w_ref, c1_ref, s1_ref, tr_ref, ti_ref, c2_ref,
           s2_ref, sc_ref, o_ref, n1=n1, n2=n2)


def _body_q(x_ref, q_ref, w_ref, c1_ref, s1_ref, tr_ref, ti_ref, c2_ref,
            s2_ref, sc_ref, o_ref, *, n1: int, n2: int):
    """int16 variant: ``q_ref`` (block_frames, 1, 1) holds the per-frame
    decode scale; one convert + one multiply in VMEM (the host decode's
    exact rounding) before the same two-stage CT chain."""
    _chain(x_ref[...].astype(jnp.float32) * q_ref[...], w_ref, c1_ref,
           s1_ref, tr_ref, ti_ref, c2_ref, s2_ref, sc_ref, o_ref,
           n1=n1, n2=n2)


def _chain(x, w_ref, c1_ref, s1_ref, tr_ref, ti_ref, c2_ref, s2_ref,
           sc_ref, o_ref, *, n1: int, n2: int):
    bf = x.shape[0]
    n2h = c2_ref.shape[1]
    a = x * w_ref[...][None]                   # x: (bf, n1, n2)
    # Stage 1 (real input): Y = D1 @ A, batched over frames.
    yr = jnp.einsum("nk,bnm->bkm", c1_ref[...], a,
                    precision=_PREC, preferred_element_type=jnp.float32)
    yi = jnp.einsum("nk,bnm->bkm", s1_ref[...], a,
                    precision=_PREC, preferred_element_type=jnp.float32)
    # Twiddle.
    tr = tr_ref[...][None]
    ti = ti_ref[...][None]
    zr = yr * tr - yi * ti
    zi = yr * ti + yi * tr
    # Stage 2: X = Z @ D2 (one-sided columns).
    xr = (jnp.einsum("bkn,nj->bkj", zr, c2_ref[...], precision=_PREC,
                     preferred_element_type=jnp.float32)
          - jnp.einsum("bkn,nj->bkj", zi, s2_ref[...], precision=_PREC,
                       preferred_element_type=jnp.float32))
    xi = (jnp.einsum("bkn,nj->bkj", zr, s2_ref[...], precision=_PREC,
                     preferred_element_type=jnp.float32)
          + jnp.einsum("bkn,nj->bkj", zi, c2_ref[...], precision=_PREC,
                       preferred_element_type=jnp.float32))
    p = xr * xr + xi * xi                      # (bf, n1, n2h)
    p = jnp.transpose(p, (0, 2, 1)) * sc_ref[...][None]
    o_ref[...] = p.reshape(bf, n2h * n1)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def ct_frame_psd(frames: jnp.ndarray, p, n1: int | None = None,
                 block_frames: int = 32, interpret: bool | None = None,
                 scales: jnp.ndarray | None = None) -> jnp.ndarray:
    """One-sided PSD of pre-framed data via two-stage CT matmuls.

    frames: (n_frames, window_size); returns (n_frames, n_bins).
    Accepts raw int16 PCM frames (``scales``: per-frame decode scales,
    (n_frames,); None = plain full-scale decode) — dequantization then
    happens in VMEM, bitwise-equal to the host decode.
    """
    if interpret is None:
        interpret = common.use_interpret()
    nfft = p.nfft
    if n1 is None:
        n1 = 1 << (int(np.log2(nfft)) + 1) // 2   # ~sqrt(N), power of two
    n2 = nfft // n1
    n2h = n2 // 2 + 1
    quantized = frames.dtype == jnp.int16

    consts = _constants(p, n1, n2)
    nf = frames.shape[0]
    fpad = common.round_up(max(nf, 1), block_frames)
    x = common.pad_axis(frames if quantized
                        else frames.astype(jnp.float32), 0, fpad)
    if p.window_size < nfft:
        x = common.pad_axis(x, 1, nfft)
    # the row-major (n1, n2) split of each frame happens here, in XLA:
    # Mosaic refuses the in-kernel (bf, nfft) -> (bf, n1, n2) shape cast
    x = x.reshape(fpad, n1, n2)

    grid = (fpad // block_frames,)
    full = lambda shape: pl.BlockSpec(shape, lambda i: tuple(0 for _ in shape))
    in_specs = [
        pl.BlockSpec((block_frames, n1, n2), lambda i: (i, 0, 0)),
        full((n1, n2)),          # window
        full((n1, n1)), full((n1, n1)),      # stage-1 DFT
        full((n1, n2)), full((n1, n2)),      # twiddle
        full((n2, n2h)), full((n2, n2h)),    # stage-2 DFT
        full((n2h, n1)),                     # scale
    ]
    operands = [x, *[jnp.asarray(c) for c in consts]]
    body = functools.partial(_body, n1=n1, n2=n2)
    if quantized:
        if scales is None:
            sq = jnp.full((nf,), common.PCM_DECODE_SCALE, jnp.float32)
        else:
            sq = jnp.asarray(scales, jnp.float32)
        sq = common.pad_axis(sq, 0, fpad).reshape(fpad, 1, 1)
        in_specs.insert(1, pl.BlockSpec((block_frames, 1, 1),
                                        lambda i: (i, 0, 0)))
        operands.insert(1, sq)
        body = functools.partial(_body_q, n1=n1, n2=n2)

    out = pl.pallas_call(
        body,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_frames, n2h * n1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((fpad, n2h * n1), jnp.float32),
        interpret=interpret,
        name="ct_frame_psd",
    )(*operands)

    return out[:nf, : p.n_bins]
