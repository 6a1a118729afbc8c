"""The generated wav corpus of a cell, made from the seed.

Each file is a Gaussian noise floor plus decaying sinusoid pings (the
signal of ``chip_smoke.py``'s corpus), written as 16-bit mono wav.  The
ping gaps and amplitudes of a file are one fixed set of values spread
over the mix's ranges and shuffled by the seed, so every seed gives the
same number of pings of the same sizes, in another order and over
another noise floor: the work of a job does not depend on the seed.
"""
from __future__ import annotations

import concurrent.futures as cf
import os
import wave

import numpy as np

BLOCK = 1 << 22          # samples drawn per noise block (one thread each)
WORKERS = 8


def file_name(i: int) -> str:
    return f"rec{i:04d}.wav"


def seed_words(seed: int) -> list[int]:
    s = int(seed) % (1 << 64)
    return [s & 0xFFFFFFFF, s >> 32]


def pings(seed: int, file_idx: int, n: int, fs: float, sig: dict
          ) -> tuple[np.ndarray, np.ndarray]:
    """Start samples and amplitudes of a file's pings."""
    lo, hi = sig["ping_gap_sec"]
    count = int(n / fs / ((lo + hi) / 2.0))
    grid = (np.arange(count) + 0.5) / max(count, 1)
    rng = np.random.default_rng(seed_words(seed) + [file_idx, 1])
    gaps = lo + (hi - lo) * rng.permutation(grid)
    alo, ahi = sig["ping_amplitude"]
    amps = alo + (ahi - alo) * rng.permutation(grid)
    starts = np.cumsum(np.round(gaps * fs).astype(np.int64))
    keep = starts + sig["ping_len"] < n
    return starts[keep], amps[keep].astype(np.float32)


def ping_shape(sig: dict) -> np.ndarray:
    t = np.arange(sig["ping_len"])
    return (np.exp(-t / sig["ping_decay_samples"])
            * np.sin(2 * np.pi * sig["ping_cycles_per_sample"] * t)
            ).astype(np.float32)


def _noise_block(seed: int, file_idx: int, b: int, n: int, rms: float,
                 out: np.ndarray) -> None:
    rng = np.random.default_rng(seed_words(seed) + [file_idx, 0, b])
    lo = b * BLOCK
    x = rng.standard_normal(min(BLOCK, n - lo), dtype=np.float32)
    x *= np.float32(rms)
    out[lo:lo + x.size] = x


def signal(seed: int, file_idx: int, n: int, fs: float, sig: dict,
           pool: cf.Executor | None = None) -> np.ndarray:
    """The float32 signal of one file (before 16-bit quantisation)."""
    x = np.empty(n, np.float32)
    blocks = range(-(-n // BLOCK))
    args = (seed, file_idx)
    if pool is None:
        for b in blocks:
            _noise_block(*args, b, n, sig["noise_rms"], x)
    else:
        list(pool.map(lambda b: _noise_block(*args, b, n,
                                             sig["noise_rms"], x), blocks))
    shape = ping_shape(sig)
    for s, a in zip(*pings(seed, file_idx, n, fs, sig)):
        x[s:s + shape.size] += a * shape
    return x


def quantise(x: np.ndarray, pool: cf.Executor | None = None) -> np.ndarray:
    """Full scale 1.0 to 16-bit PCM, clipped."""
    out = np.empty(x.size, "<i2")

    def block(b):
        sl = slice(b * BLOCK, (b + 1) * BLOCK)
        out[sl] = np.clip(x[sl] * np.float32(32767.0), -32768, 32767)
    blocks = range(-(-x.size // BLOCK))
    list(pool.map(block, blocks) if pool else map(block, blocks))
    return out


def write_corpus(root: str, config: dict, mix: dict, seed: int) -> list[str]:
    """Write the cell's ``n_files`` wav files under ``root``."""
    os.makedirs(root, exist_ok=True)
    fs = config["fs"]
    n = int(round(config["file_sec"] * fs))
    paths = []
    with cf.ThreadPoolExecutor(WORKERS) as pool:
        for i in range(config["n_files"]):
            pcm = quantise(signal(seed, i, n, fs, mix["signal"], pool), pool)
            path = os.path.join(root, file_name(i))
            with open(path, "wb") as f:
                with wave.open(f, "wb") as w:
                    w.setnchannels(1)
                    w.setsampwidth(2)
                    w.setframerate(int(fs))
                    w.writeframes(pcm.tobytes())
                # on disk before the window opens: the write-back of
                # the corpus must not contend with the stores' fsyncs
                f.flush()
                os.fsync(f.fileno())
            paths.append(path)
    return paths


def read_file(root: str, i: int) -> np.ndarray:
    """One file's samples as int16."""
    with wave.open(os.path.join(root, file_name(i)), "rb") as w:
        return np.frombuffer(w.readframes(w.getnframes()), "<i2")
