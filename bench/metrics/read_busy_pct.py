"""Share of the traced job in which at least one read of the wav source
ran (the union over the loader's threads), in percent."""


def read(run):
    return run.host_busy_pct(("fetch",))
