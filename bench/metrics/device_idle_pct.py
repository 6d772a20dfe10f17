"""Share of the traced window in which no program and no op ran on the
device."""
from bench import devtrace


def read(r):
    if r.trace is None:
        return None
    return 100.0 * (1.0 - devtrace.busy_seconds(r.trace) / r.trace.window.dur)
