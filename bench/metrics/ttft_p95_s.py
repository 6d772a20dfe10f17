"""95th percentile over every request due inside the window of its first
token's time minus its due time; a request that failed or gave no first
token by the end of the drain counts as missing the tail."""
from bench import stats


def read(r):
    t = stats.ttfts(r.served(), r.run.window)
    return stats.percentile(t, 95) if t else None
