"""The work a kernel's call must do, from the call's shapes and the
parameters alone: operations and bytes of the mathematics, not of an
implementation.

A frame's power spectrum counts as a real FFT of ``nfft`` points,
2.5 nfft log2(nfft) operations, plus the window multiply, the squared
magnitude (three per bin) and the density scale (one per bin), whatever
computes it: the direct DFT matmul of ``frame_psd`` and ``welch_psd``
or the Cooley-Tukey matmuls of ``ct_frame_psd``.  Bytes are each operand
read once and each result written once at its dtype: the records'
samples (2 bytes each on the int16 transport), one decode scale per
record, and the float32 results.  So a re-tiling, another algorithm or
another precision leaves the count where it is.
"""
from __future__ import annotations

import dataclasses
import math

F32 = 4


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def least_time(self, peak: dict) -> tuple[float, str]:
        """The least time on a chip of ``peak``, and what bounds it."""
        compute = self.flops / peak["flops_per_s"]
        memory = self.bytes / peak["bytes_per_s"]
        return (compute, "compute") if compute >= memory \
            else (memory, "memory")


def _frame_flops(p) -> float:
    return (2.5 * p.nfft * math.log2(p.nfft) + p.window_size
            + 4 * p.n_bins)


def _signal_bytes(n_records: int, p, sample_bytes: int) -> float:
    scale = F32 if sample_bytes == 2 else 0
    return n_records * (p.record_size * sample_bytes + scale)


def frame_psd(n_records: int, p, sample_bytes: int) -> Work:
    """Per-frame PSD of ``n_records`` records: (frames, n_bins) each."""
    frames = n_records * p.frames_per_record
    return Work(frames * _frame_flops(p),
                _signal_bytes(n_records, p, sample_bytes)
                + frames * p.n_bins * F32)


def welch_psd(n_records: int, p, sample_bytes: int) -> Work:
    """Fused Welch PSD: every frame's PSD, summed into one row."""
    frames = n_records * p.frames_per_record
    return Work(frames * (_frame_flops(p) + p.n_bins),
                _signal_bytes(n_records, p, sample_bytes)
                + n_records * p.n_bins * F32)


def records_of_frames(n_frames: int, p) -> int:
    """Whole records behind a call that takes frames (``ct_frame_psd``)."""
    if n_frames % p.frames_per_record:
        raise ValueError(f"{n_frames} frames are not whole records of "
                         f"{p.frames_per_record} frames")
    return n_frames // p.frames_per_record
