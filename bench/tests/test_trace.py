"""The reduction from a profiler trace to the per-layer metrics, on a
trace recorded on the chip (``data/``, made by tracing four steps of a
``set1.full`` job with the benchmark's own spans on)."""
import json
import pathlib

import pytest

from bench.harness import work
from bench.harness.spec import Cell
from bench.harness.trace import TracedRun, gaps, union_s


def test_union_and_gaps():
    iv = [(0, 10), (5, 20), (30, 40), (35, 36)]
    assert union_s(iv, 0, 100) == 30 / 1e9
    assert union_s(iv, 8, 32) == 14 / 1e9
    assert gaps(iv, 0, 50) == [(20, 30), (40, 50)]
    assert gaps([], 0, 5) == [(0, 5)]


DATA = pathlib.Path(__file__).parent / "data"


@pytest.fixture(scope="module")
def traced():
    """Four steps of a set-1 full-product job, traced on one TPU v5e."""
    meta = json.loads((DATA / "set1.full.json").read_text())
    return meta, TracedRun(str(DATA / "set1.full.xplane.pb"),
                           Cell("set1.full"), meta["modules"], meta["steps"],
                           meta["device_kind"], 1)


def test_device_share_of_a_chip_trace(traced):
    meta, run = traced
    assert 0 < run.busy_s() <= run.window_s
    assert 0 <= run.device_idle_pct() < 100


def test_programs_and_kernels_of_a_chip_trace(traced):
    meta, run = traced
    step, reduce = (run.module_ms_per_step(w) for w in ("step", "reduce"))
    assert step > 0 and reduce > 0
    assert step * meta["steps"] / 1e3 <= run.busy_s()
    for kernel in ("welch_psd", "frame_psd", "detect_events"):
        assert len(run._kernel_events(kernel)[0]) == meta["steps"]
    assert run.kernel_ms_per_step("detect_events") < step
    assert run.kernel_ms_per_step("ct_frame_psd") is None


@pytest.mark.parametrize("kernel", ["welch_psd", "frame_psd"])
def test_roofline_shares_of_a_chip_trace(traced, kernel):
    _, run = traced
    share = run.roofline_pct(kernel, getattr(work, kernel))
    assert 0 < share <= 100
    assert run.bounds[kernel] == "memory"
    assert run.roofline_pct("ct_frame_psd", work.frame_psd) is None


def test_host_spans_of_a_chip_trace(traced):
    _, run = traced
    assert 0 < run.host_busy_pct(("fetch",)) <= 100
    assert 0 < run.host_busy_pct(("write", "commit")) <= 100
    assert run.host_busy_pct(("no_such_span",)) is None
    out = run.breakdown()
    assert 0 < len(out["device_ops"]) <= 10
    assert 0 < len(out["idle_gaps"]) <= 10
    assert all(s > 0 for _, s in out["device_ops"] + out["idle_gaps"])
