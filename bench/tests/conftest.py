"""The benchmark's own tests run on the CPU, with the Pallas kernels in
interpret mode and four host devices for the four-chip layout:

    python -m pytest bench/tests
"""
import os
import pathlib
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
