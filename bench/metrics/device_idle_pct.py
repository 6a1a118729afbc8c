"""Share of the traced job in which no operation ran on a chip,
averaged over the cell's chips, in percent."""


def read(run):
    return run.device_idle_pct()
