"""The kernels' work functions count the mathematics of a call from its
shapes and the parameters, whatever implements it."""
import dataclasses
import inspect

import jax
import jax.numpy as jnp
import pytest

from bench.harness import peaks, work
from repro.core.params import PARAM_SET_1, PARAM_SET_2
from repro.kernels import ops


def test_work_functions_take_no_backend_or_tiling():
    for fn in (work.frame_psd, work.welch_psd):
        names = set(inspect.signature(fn).parameters)
        assert not names & {"backend", "block_frames", "block_bins",
                            "chunk_frames", "n1", "precision", "interpret"}


def test_set2_frame_psd_work_is_the_same_on_both_paths():
    """The same records go to the Cooley-Tukey kernel (the set-2 default)
    and to the direct-DFT kernel; the work of the call is one number."""
    p = dataclasses.replace(PARAM_SET_2, record_size_sec=0.25)
    x = jnp.zeros((3, p.record_size), jnp.int16)
    s = jnp.ones((3,), jnp.float32)
    counted = {}
    for backend, kernel in (("ct", "ct_frame_psd"), ("direct", "frame_psd")):
        jaxpr = str(jax.make_jaxpr(
            lambda x, s: ops.frame_psd(x, p, backend=backend, scales=s))(x, s))
        assert f"name={kernel}" in jaxpr
        out = ops.frame_psd(x, p, backend=backend, scales=s)
        assert out.shape == (3, p.frames_per_record, p.n_bins)
        frames_in = (x.shape[0] * p.frames_per_record if backend == "ct"
                     else None)
        n_rec = (work.records_of_frames(frames_in, p) if frames_in
                 else x.shape[0])
        counted[backend] = work.frame_psd(n_rec, p, x.dtype.itemsize)
    assert counted["ct"] == counted["direct"]


def test_frame_and_welch_work_at_the_paper_sizes():
    p = PARAM_SET_1
    fr = work.frame_psd(8, p, 2)
    frames = 8 * 15359
    assert fr.flops == frames * (2.5 * 256 * 8 + 256 + 4 * 129)
    assert fr.bytes == 8 * (p.record_size * 2 + 4) + frames * 129 * 4
    we = work.welch_psd(8, p, 2)
    assert we.flops == fr.flops + frames * 129
    assert we.bytes == 8 * (p.record_size * 2 + 4) + 8 * 129 * 4
    # the int16 transport reads half the bytes of the float32 one
    assert work.welch_psd(8, p, 4).bytes > we.bytes


def test_roofline_bound_and_peaks():
    peak = peaks.peaks("TPU v5 lite")
    assert peak == {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    t, bound = work.welch_psd(8, PARAM_SET_1, 2).least_time(peak)
    assert bound == "memory" and t == pytest.approx(
        work.welch_psd(8, PARAM_SET_1, 2).bytes / 819e9)
    t, bound = work.Work(1e15, 1.0).least_time(peak)
    assert bound == "compute"
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("cpu")


def test_frames_of_whole_records_only():
    assert work.records_of_frames(160, PARAM_SET_2) == 2
    with pytest.raises(ValueError):
        work.records_of_frames(81, PARAM_SET_2)
