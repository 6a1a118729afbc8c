"""The fused Welch kernel's share of its roofline, in percent."""
from bench.harness import work


def read(run):
    return run.roofline_pct("welch_psd", work.welch_psd)
