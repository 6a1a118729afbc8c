"""The timing wrappers are pure pass-throughs: a job's committed store
is bitwise the same with and without them."""
import filecmp
import os

import numpy as np
import pytest

from bench.harness import corpus
from bench.harness.timing import JobRecord, TimedSink, TimedSource
from bench.harness.window import params
from bench.tests.tiny import tiny_cell
from repro import api
from repro.api.sinks import StoreSink
from repro.core.store import FeatureStore


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    cell = tiny_cell("set1.full")
    root = str(tmp_path_factory.mktemp("corpus"))
    corpus.write_corpus(root, cell.config, cell.mix, 2**31 + 3)
    return cell, root


def job(cell, root, store, payload, wrapped, limit=None):
    cfg, mix = cell.config, cell.mix
    p = params(cfg)
    m = api.scan_dataset(root, p.record_size, seed=42)
    rec = JobRecord(m.n_records)
    source = api.WavSource(root)
    sink = StoreSink(FeatureStore(store))
    if wrapped:
        source, sink = TimedSource(source, rec), TimedSink(sink, rec)
    j = (api.job(m, p).features(*mix["features"])
         .chunk(cfg["chunk_records"]).window(per_file=True)
         .source(source).payload(payload)
         .events(cfg["event_threshold_db"],
                 hysteresis_db=cfg["event_hysteresis_db"], impulsive=True)
         .to(sink).async_io().limit(limit))
    j.run()
    return rec


def same_store(a, b):
    names = sorted(n for n in os.listdir(a) if n.endswith((".npy", ".bin")))
    assert names == sorted(n for n in os.listdir(b)
                           if n.endswith((".npy", ".bin")))
    assert names
    for n in names:
        assert filecmp.cmp(os.path.join(a, n), os.path.join(b, n),
                           shallow=False), n


@pytest.mark.parametrize("payload", ["int16", "float32"])
def test_wrapped_store_is_bitwise_equal(data, tmp_path, payload):
    cell, root = data
    job(cell, root, str(tmp_path / "plain"), payload, wrapped=False)
    rec = job(cell, root, str(tmp_path / "timed"), payload, wrapped=True)
    same_store(str(tmp_path / "plain"), str(tmp_path / "timed"))
    # the wrappers saw every record read and committed
    committed = np.concatenate([r for _, r, _ in rec.commits])
    assert sorted(committed) == list(range(rec.first_fetch.size))
    assert np.isfinite(rec.first_fetch).all()
    lags = np.concatenate([lag for _, _, lag in rec.commits])
    assert (lags > 0).all()


def test_resumed_wrapped_store_is_bitwise_equal(data, tmp_path):
    cell, root = data
    job(cell, root, str(tmp_path / "plain"), "int16", wrapped=False)
    job(cell, root, str(tmp_path / "timed"), "int16", wrapped=True, limit=1)
    rec = job(cell, root, str(tmp_path / "timed"), "int16", wrapped=True)
    same_store(str(tmp_path / "plain"), str(tmp_path / "timed"))
    # the resumed job read and committed only what was left
    assert not np.isfinite(rec.first_fetch).all()
