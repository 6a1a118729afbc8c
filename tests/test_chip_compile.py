"""Compile the main-path kernels and the jitted step for a TPU v5e.

Nothing runs: the TPU compiler, which is installed with jax, compiles
for a described ``v5e:2x2`` topology at the paper's widths (fs 32,768
Hz; set 1 with 60 s records, set 2 with 10 s records).  These are the
refusals interpret mode cannot show: block shapes the TPU lowering
rejects, primitives Mosaic cannot lower, and kernels XLA cannot
partition over a mesh.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every pytest worker
imports this file.  Interpret mode is switched off per test with
``monkeypatch``, and the jit caches are cleared on both sides so no
TPU-lowered trace leaks into a later CPU test (or the reverse).
"""
import collections
import math
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (AxisType, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.api import Window, engine, resolve_features
from repro.api.features import _percentile_taps, selects_order_stats
from repro.core.manifest import DatasetManifest
from repro.core.params import PARAM_SET_1, PARAM_SET_2
from repro.core.tol import band_matrix
from repro.kernels import common, ct_rfft, events, framepsd, select, tol

RECORDS = 8
# records a step in each paper set's benchmark configuration
CHUNK = {1: 8, 2: 32}
# every feature of the smoke job; events adds the impulsive metrics
FEATURES = ("welch", "spl", "tol", "percentiles", "ltsa", "spd", "minmax",
            "events", "impulsive")
KERNELS = {1: {"welch_psd", "frame_psd", "tol_levels", "detect_events",
               "select_order_stats"},
           2: {"ct_frame_psd", "welch_mean", "tol_levels",
               "detect_events"}}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                           # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,),
                         devices=topo.devices)


@pytest.fixture
def compiled_for_tpu(monkeypatch):
    """Kernels lower through Mosaic, not the interpreter."""
    jax.clear_caches()
    engine.compile_step.cache_clear()
    monkeypatch.setattr(common, "use_interpret", lambda: False)
    yield
    jax.clear_caches()
    engine.compile_step.cache_clear()


def folded_constants(hlo_text: str) -> collections.Counter:
    """(dtype, literal) of every constant in a compiled program."""
    return collections.Counter(re.findall(
        r"= (\w+)\[[^\]]*\][^ ]* constant\((.*?)\)(?:, metadata|$)",
        hlo_text, re.M))


def compile_kernels(fn, *shapes) -> collections.Counter:
    return common.tpu_kernel_calls(
        jax.jit(fn).lower(*shapes).compile().as_text())


def spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.usefixtures("compiled_for_tpu")
class TestKernelsCompileForV5e:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.int16],
                             ids=["float32", "int16"])
    def test_welch_psd_set1(self, one_chip, dtype):
        p = PARAM_SET_1
        x = spec(one_chip, (RECORDS, p.record_size), dtype)
        q = spec(one_chip, (RECORDS,), jnp.float32)
        calls = compile_kernels(
            lambda x, q: framepsd.welch_psd(
                x, p, scales=q if dtype == jnp.int16 else None), x, q)
        assert calls == {"welch_psd": 1}

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.int16],
                             ids=["float32", "int16"])
    def test_ct_frame_psd_set2(self, one_chip, dtype):
        p = PARAM_SET_2
        nf = RECORDS * p.frames_per_record
        x = spec(one_chip, (nf, p.window_size), dtype)
        q = spec(one_chip, (nf,), jnp.float32)
        calls = compile_kernels(
            lambda x, q: ct_rfft.ct_frame_psd(
                x, p, scales=q if dtype == jnp.int16 else None), x, q)
        assert calls == {"ct_frame_psd": 1}

    def test_frame_psd_set1(self, one_chip):
        p = PARAM_SET_1
        assert p.frames_per_record == 15359
        x = spec(one_chip, (RECORDS, p.record_size), jnp.int16)
        q = spec(one_chip, (RECORDS,), jnp.float32)
        calls = compile_kernels(
            lambda x, q: framepsd.frame_psd(x, p, scales=q), x, q)
        assert calls == {"frame_psd": 1}

    def test_tol_levels_set1(self, one_chip):
        p = PARAM_SET_1
        bm = jnp.asarray(band_matrix(p))
        psd = spec(one_chip, (RECORDS, p.n_bins), jnp.float32)
        calls = compile_kernels(lambda s: tol.tol_levels(s, bm, p), psd)
        assert calls == {"tol_levels": 1}

    def test_select_order_stats_set1(self, one_chip):
        p = PARAM_SET_1
        db = spec(one_chip, (RECORDS, p.frames_per_record, p.n_bins),
                  jnp.float32)
        lo, hi, _, _ = _percentile_taps(p.frames_per_record)
        calls = compile_kernels(
            lambda x: select.select_order_stats(x, tuple(lo.tolist()),
                                                tuple(hi.tolist())), db)
        assert calls == {"select_order_stats": 1}

    def test_detect_events_set1(self, one_chip):
        p = PARAM_SET_1
        shape = (RECORDS, p.frames_per_record)
        calls = compile_kernels(
            lambda s, b: events.detect_events(
                s, b, threshold_db=p.event_threshold_db,
                hysteresis_db=p.event_hysteresis_db,
                capacity=p.event_capacity),
            spec(one_chip, shape, jnp.float32),
            spec(one_chip, shape, jnp.int32))
        assert calls == {"detect_events": 1}


def two_files(p):
    return DatasetManifest(n_files=2,
                           records_per_file=int(45 * 60 // p.record_size_sec),
                           record_size=p.record_size, fs=p.fs, seed=42)


def step_args(p, sharding, lead):
    return (spec(sharding, lead + (p.record_size,), jnp.int16),
            spec(sharding, lead, jnp.float32),
            spec(sharding, lead, jnp.bool_))


def compile_step(p, sharding, mesh=None, n_shards=1, chunk=4):
    """The engine's int16-transport step for every smoke feature, over
    two 45-minute files."""
    step = engine.compile_step(tuple(resolve_features(FEATURES)),
                               two_files(p), p, mesh, ("data",), True, False,
                               True, "int16")
    return step.lower(*step_args(p, sharding, (n_shards, chunk))).compile()


def compile_reduce_update(p, sharding, chunk):
    """The engine's carry update for every smoke feature's reductions,
    per-file windows, fed the shapes the step hands it."""
    m = two_files(p)
    specs = tuple(resolve_features(FEATURES))
    step = engine.compile_step(specs, m, p, None, ("data",), True, False,
                               True, "int16")
    lead = (1, chunk)
    out = jax.eval_shape(step, *step_args(p, sharding, lead))
    bindings, windows = engine.resolve_bindings(specs, m, p,
                                                Window("file"))
    state = jax.eval_shape(
        lambda: engine._init_reduce_state(bindings, None))
    on_chip = lambda x: spec(sharding, x.shape, x.dtype)   # noqa: E731
    update = engine.compile_reduce_update(bindings, None, ("data",))
    return update.lower(
        jax.tree.map(on_chip, state), jax.tree.map(on_chip, out),
        spec(sharding, lead, jnp.bool_),
        {k: spec(sharding, lead, jnp.int32) for k in windows}).compile()


def scatter_updates(hlo_text: str) -> list[int]:
    """Element count of the updates operand of every scatter in a
    compiled program, fused or not."""
    shapes = dict(re.findall(r"(%[\w.-]+) = \w+\[([\d,]*)\]", hlo_text))
    sizes = []
    for operands in re.findall(r"= \w+\[[\d,]*\][^ ]* scatter\(([^)]*)\)",
                               hlo_text):
        dims = shapes[operands.split(", ")[2]]
        sizes.append(math.prod(int(d) for d in dims.split(",") if d))
    return sizes


@pytest.mark.usefixtures("compiled_for_tpu")
class TestStepCompilesForV5e:
    @pytest.mark.parametrize("param_set", [1, 2])
    def test_one_chip(self, one_chip, param_set):
        p = PARAM_SET_1 if param_set == 1 else PARAM_SET_2
        compiled = compile_step(p, one_chip)
        text = compiled.as_text()
        calls = common.tpu_kernel_calls(text)
        assert calls == dict.fromkeys(KERNELS[param_set], 1)
        # set 1's 15,359 frames a record select the percentiles' order
        # statistics; set 2's 80 sort
        selects = selects_order_stats(p.frames_per_record)
        assert selects == (param_set == 1)
        assert (" sort(" in text) == (not selects)
        # fits a 16 GB chip with room for the pipeline's in-flight steps
        assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30

    def test_four_chip_mesh(self, one_chip, four_chips):
        """The step is a shard_map: every device runs each kernel once
        on its own shard slice, and no collective moves the payload.
        It folds the same constants as the one-chip step — the two
        compilations once folded a percentile weight differently, which
        broke bitwise equality across device counts on the chip."""
        sharding = NamedSharding(four_chips, P(("data",)))
        text = compile_step(PARAM_SET_1, sharding, mesh=four_chips,
                            n_shards=4, chunk=2).as_text()
        single = compile_step(PARAM_SET_1, one_chip, chunk=2).as_text()
        assert folded_constants(text) == folded_constants(single)
        assert common.tpu_kernel_calls(text) == dict.fromkeys(KERNELS[1], 1)
        for op in ("all-gather", "all-reduce", "all-to-all",
                   "collective-permute", "reduce-scatter"):
            assert op not in text, op
        # each device's parameter is its own (1, chunk, record) slice
        assert f"s16[1,2,{PARAM_SET_1.record_size}]" in text


@pytest.mark.usefixtures("compiled_for_tpu")
class TestReduceUpdateCompilesForV5e:
    @pytest.mark.parametrize("param_set", [1, 2])
    def test_one_chip(self, one_chip, param_set):
        """The SPD histogram is a dense count: no scatter takes one
        update per spectrogram cell (the per-window sum over records
        still scatters a record's histogram), and the count leaves the
        one-hot over dB bins unmaterialised."""
        p = PARAM_SET_1 if param_set == 1 else PARAM_SET_2
        chunk = CHUNK[param_set]
        compiled = compile_reduce_update(p, one_chip, chunk)
        cells = chunk * p.frames_per_record * p.n_bins
        sizes = scatter_updates(compiled.as_text())
        assert all(n < cells for n in sizes), (sizes, cells)
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
