"""Share of the traced job spent inside the store sink's write,
write_windows, write_events and commit (the sink's writer thread), in
percent."""


def read(run):
    return run.host_busy_pct(("write", "write_windows", "write_events",
                              "commit"))
