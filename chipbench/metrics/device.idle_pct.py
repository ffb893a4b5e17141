"""Share of the traced slice of the window in which no op ran on the
device: 1 - (union of the device's op intervals) / slice, from the
profiler trace."""


def read(run):
    if run.trace is None:
        return None
    return run.trace["idle_pct"]
