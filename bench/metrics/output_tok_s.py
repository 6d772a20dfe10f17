"""Output tokens emitted inside the window, per second of the window."""
from bench import stats


def read(r):
    return stats.output_tokens(r.served(), r.run.window) / r.run.window.seconds
