"""Device time of the feature-step program per step and chip, in ms,
found by the program's module name in the trace."""


def read(run):
    return run.module_ms_per_step("step")
