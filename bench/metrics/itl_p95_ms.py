"""95th percentile of every gap between consecutive output tokens of one
request, both inside the window, in milliseconds."""
from bench import stats


def read(r):
    g = stats.gaps(r.served(), r.run.window)
    return 1e3 * stats.percentile(g, 95) if g else None
