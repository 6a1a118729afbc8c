"""Device time of the event detector's kernel per step and chip, in ms.
The Schmitt-trigger scan is bound by latency, so a roofline share would
say nothing."""


def read(run):
    return run.kernel_ms_per_step("detect_events")
