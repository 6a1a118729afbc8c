"""Shared helpers for the DEPAM Pallas kernels.

All kernels target TPU (v5e: 16 MB VMEM/core, 128x128 MXU, 8x128 VPU lanes)
and are validated on CPU with ``interpret=True``.  ``use_interpret()`` picks
interpret mode automatically when no TPU is present so the same call sites
work in tests, benchmarks and on real hardware.
"""
from __future__ import annotations

import collections
import functools
import re

import jax
import numpy as np

from repro.core.params import PCM_DECODE_SCALE


@functools.cache
def use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def tpu_kernel_calls(hlo_text: str) -> collections.Counter:
    """Pallas kernels in a compiled TPU program (``compiled.as_text()``),
    counted by the ``name=`` each ``pallas_call`` was given.  A
    ``tpu_custom_call`` without a pallas_call name counts under "?".
    An interpret-mode program holds none."""
    names = collections.Counter()
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            hit = re.search(r'op_name="[^"]*/(\w+)/pallas_call"', line)
            names[hit.group(1) if hit else "?"] += 1
    return names


def dequantize(pcm, scales=None):
    """int16 PCM -> float32 waveform, bitwise-matching the host decode.

    ``scales`` is the per-record float32 decode-scale sidecar
    (PCM_DECODE_SCALE * calibration gain, fused in float32 on the host
    — see ``data.wavio``), shaped like ``pcm`` minus its trailing sample
    axis; ``None`` means plain full-scale decode.  One int16->float32
    convert (exact) plus ONE float32 multiply — the same single rounding
    the host float path performs, so the two transports agree bitwise.
    Used by the XLA fallback path; the Pallas kernels inline the same
    two ops per block so the float32 waveform never exists in HBM.
    """
    import jax.numpy as jnp

    w = pcm.astype(jnp.float32)
    if scales is None:
        return w * jnp.float32(PCM_DECODE_SCALE)
    s = jnp.asarray(scales, jnp.float32)
    return w * s[..., None]


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def pad_axis(x, axis: int, target: int):
    """Zero-pad axis of ndarray/jnp array up to ``target`` length."""
    pad = target - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    import jax.numpy as jnp

    return jnp.pad(x, widths)


def dft_matrices(n_in: int, nfft: int, window: np.ndarray,
                 dtype=np.float32) -> tuple[np.ndarray, np.ndarray]:
    """Window-folded real-DFT matrices.

    Returns (C, S), each (n_in, n_bins) with
      C[j, k] =  window[j] * cos(2 pi j k / nfft)
      S[j, k] = -window[j] * sin(2 pi j k / nfft)
    so that for a real frame f:  rfft(window*f, nfft) = f@C + 1j*(f@S).
    """
    n_bins = nfft // 2 + 1
    j = np.arange(n_in)[:, None].astype(np.float64)
    k = np.arange(n_bins)[None, :].astype(np.float64)
    ang = 2.0 * np.pi * j * k / nfft
    c = (window[:, None] * np.cos(ang)).astype(dtype)
    s = (-window[:, None] * np.sin(ang)).astype(dtype)
    return c, s
