"""The direct-DFT per-frame PSD kernel's share of its roofline, in
percent."""
from bench.harness import work


def read(run):
    return run.roofline_pct("frame_psd", work.frame_psd)
