"""Median, over every request due inside the window and admitted, of its
admission to a slot (the engine's ``admit_time`` stamp) minus its due
time."""
from bench import stats


def read(r):
    w = stats.queue_waits(r.served(), r.run.window)
    return stats.percentile(w, 50) if w else None
