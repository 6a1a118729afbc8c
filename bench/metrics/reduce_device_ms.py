"""Device time of the reduce-update program per step and chip, in ms
(on four chips it holds the one combine), found by its module name."""


def read(run):
    return run.module_ms_per_step("reduce")
