"""End-to-end behaviour tests for the paper's system."""
import os
import subprocess
import sys

import numpy as np
import pytest
import scipy.signal as ss

import jax
import jax.numpy as jnp

from repro.core import pipeline
from repro.core.manifest import DatasetManifest
from repro.core.params import DepamParams, PARAM_SET_1, PARAM_SET_2


class TestDepamEndToEnd:
    """The paper's job: raw records in, (Welch, SPL, TOL, LTSA) out."""

    def test_full_chain_vs_scipy(self):
        p = DepamParams(nfft=256, window_size=256, window_overlap=128,
                        record_size_sec=1.0)
        m = DatasetManifest(n_files=2, records_per_file=3,
                            record_size=p.record_size, fs=p.fs, seed=1)
        out = pipeline.run_pipeline(m, p, chunk_records=3)
        assert out["ltsa_db"].shape == (6, p.n_bins)
        assert out["tol"].shape[0] == 6
        for i in range(6):
            rec = np.asarray(pipeline.synth_record(jnp.int32(i), m))
            _, want = ss.welch(rec, fs=p.fs, window=p.window,
                               nperseg=p.window_size,
                               noverlap=p.window_overlap, nfft=p.nfft,
                               detrend=False, scaling="density")
            assert np.allclose(out["welch"][i], want, rtol=5e-3, atol=1e-8)

    def test_both_paper_parameter_sets_run(self):
        for base in (PARAM_SET_1, PARAM_SET_2):
            p = DepamParams(nfft=base.nfft, window_size=base.window_size,
                            window_overlap=base.window_overlap,
                            record_size_sec=1.0)
            m = DatasetManifest(n_files=1, records_per_file=2,
                                record_size=p.record_size, fs=p.fs)
            out = pipeline.run_pipeline(m, p, chunk_records=2)
            assert np.isfinite(out["spl"]).all()
            assert out["welch"].shape == (2, p.n_bins)

    def test_epoch_aggregate_is_mean_spectrum(self):
        p = DepamParams(nfft=128, window_size=128, window_overlap=64,
                        record_size_sec=0.5)
        m = DatasetManifest(n_files=1, records_per_file=5,
                            record_size=p.record_size, fs=p.fs)
        out = pipeline.run_pipeline(m, p, chunk_records=2)
        want = out["welch"].mean(axis=0)
        np.testing.assert_allclose(out["mean_welch"], want, rtol=1e-5)


class TestShardedEquivalence:
    """Results must not depend on the shard count (subprocess: needs a
    multi-device jax runtime, which other tests avoid)."""

    def test_four_shards_equal_one(self):
        code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax, jax.numpy as jnp
from repro.core import pipeline
from repro.core.manifest import DatasetManifest
from repro.core.params import DepamParams
p = DepamParams(nfft=128, window_size=128, window_overlap=64,
                record_size_sec=0.25)
m = DatasetManifest(n_files=2, records_per_file=4,
                    record_size=p.record_size, fs=p.fs, seed=3)
mesh = jax.make_mesh((4,), ("data",))
single = pipeline.run_pipeline(m, p, chunk_records=2)
sharded = pipeline.run_pipeline(m, p, mesh=mesh, data_axes=("data",),
                                chunk_records=2)
assert np.allclose(single["welch"], sharded["welch"], rtol=1e-5), "welch"
assert np.allclose(single["mean_welch"], sharded["mean_welch"],
                   rtol=1e-5), "mean"
print("SHARDED-OK")
"""
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                         "src")
        # the forced host devices are CPU devices; on a machine with an
        # accelerator the child must not try to take it
        env["JAX_PLATFORMS"] = "cpu"
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=900)
        assert "SHARDED-OK" in out.stdout, out.stderr[-2000:]


class TestServing:
    """The multi-tenant service driver end to end: concurrent tenants
    (batch + live) drain through one device with results verified
    bitwise-identical to solo runs inside serve.run(--verify)."""

    def test_service_driver_verifies_bitwise(self):
        from repro.launch import serve

        results, svc = serve.run(tenants=2, live=1, files=2,
                                 records_per_file=4, record_sec=0.25,
                                 features=("welch", "spl"), chunk=4,
                                 verify=True, timeout=300.0)
        assert sorted(results) == ["batch-0", "batch-1", "live-0"]
        for r in results.values():
            assert np.isfinite(r["welch"]).all()
        # same-config batch tenants share one compiled step program
        assert svc.stats()["compile"]["step"]["hits"] >= 1
