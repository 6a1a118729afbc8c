"""How ``correct`` is decided, at a tiny size on the CPU: the program
(kernels in interpret mode) agrees with the float64 reference on every
output of every cell; the control, the reference one precision below
the configuration's, does not; and neither does a run whose timed path
carries one of the faults a cell can have."""
import jax
import jax.numpy as jnp
import pytest

from bench.harness import check, control, corpus, measure
from bench.tests.tiny import LAYOUTS, tiny_cell
from repro.api import engine

CELLS = sorted(LAYOUTS)


def run(cell, seed=2**31 + 5):
    return measure.run(cell, seed, 1.0, False, jax.devices()[:cell.chips],
                       measure.now())


@pytest.mark.parametrize("name", CELLS)
def test_program_agrees_with_the_reference(name):
    result, lines = run(tiny_cell(name))
    assert result["correct"], lines
    assert result["attempted"] > 0
    assert all(v["value"] is not None for v in result["compared"].values())


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name, tmp_path):
    cell = tiny_cell(name)
    corpus.write_corpus(str(tmp_path), cell.config, cell.mix, 17)
    ref = check.Reference(str(tmp_path), cell.config, cell.mix, 17)
    got, _ = check.readings(control.control_outputs(str(tmp_path), ref), ref)
    ok, lines = check.verdict(got, cell.limits)
    assert not ok, lines


def _state_unchanged(real):
    def build(bindings, mesh, data_axes, donate=False):
        return lambda state, out, mask, wids: state
    return build


def _half_batch(real):
    def build(bindings, mesh, data_axes, donate=False):
        fn = real(bindings, mesh, data_axes, donate)

        def update(state, out, mask, wids):
            keep = jnp.arange(mask.shape[-1]) < mask.shape[-1] // 2
            return fn(state, out, mask & keep, wids)
        return update
    return build


def _altered_answer(real):
    def build(*key):
        fn = real(*key)

        def step(*args):
            out = dict(fn(*args))
            out["welch"] = out["welch"].at[..., 5].multiply(1.01)
            return out
        return step
    return build


def _no_exchange(seg_op, contribs, wids, n_windows, n_shards, combine):
    c = contribs.reshape((n_shards, -1) + contribs.shape[1:])
    return seg_op(c[0], wids[0], num_segments=n_windows)


FAULTS = {
    "state_unchanged": ("compile_reduce_update", _state_unchanged),
    "half_batch": ("compile_reduce_update", _half_batch),
    "altered_answer": ("compile_step", _altered_answer),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_a_fault_is_not_correct(name, fault, monkeypatch):
    attr, make = FAULTS[fault]
    monkeypatch.setattr(engine, attr, make(getattr(engine, attr)))
    result, lines = run(tiny_cell(name))
    assert not result["correct"], lines


def test_a_missing_exchange_is_not_correct(monkeypatch):
    """Only the four-chip layout exchanges partials between chips."""
    monkeypatch.setattr(engine, "_merged_segments", _no_exchange)
    # build the reduce update afresh: a cached one holds the exchange
    monkeypatch.setattr(engine, "compile_reduce_update",
                        engine.compile_reduce_update.__wrapped__)
    result, lines = run(tiny_cell("set1x4.full"))
    assert not result["correct"], lines
