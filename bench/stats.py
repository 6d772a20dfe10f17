"""Exact statistics over a run's own timestamps, and the window's accounting.

Every time here is on the harness's clock (``time.perf_counter``), read when
``PagedServeEngine.step`` has returned: the step syncs on its argmax, so the
device work of the tokens it emitted is done by then.
"""
from __future__ import annotations

import dataclasses
import math


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile over every sample: the smallest value with at
    least ``q`` percent of the samples at or below it.  ``inf`` (a failed
    request) sorts last, so failures count as missing the tail."""
    if not values:
        raise ValueError("percentile of no samples")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


@dataclasses.dataclass
class Served:
    """What one request did, on the harness's clock.  ``due`` is absolute
    (None in a closed loop, where the request is sent when it is made)."""

    due: float | None
    sent: float
    token_times: list[float] = dataclasses.field(default_factory=list)
    ok: bool | None = None      # None while in flight
    admitted: float | None = None
    slot: int | None = None     # the engine slot it was first placed in


@dataclasses.dataclass
class Window:
    start: float
    end: float

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def holds(self, t: float) -> bool:
        return self.start <= t < self.end


def output_tokens(served: list[Served], w: Window) -> int:
    """Output tokens emitted inside the window."""
    return sum(1 for s in served for t in s.token_times if w.holds(t))


def gaps(served: list[Served], w: Window) -> list[float]:
    """Every gap between consecutive output tokens of one request where both
    tokens fall inside the window."""
    out = []
    for s in served:
        ts = s.token_times
        out.extend(b - a for a, b in zip(ts, ts[1:])
                   if w.holds(a) and w.holds(b))
    return out


def due_in(served: list[Served], w: Window) -> list[Served]:
    return [s for s in served if s.due is not None and w.holds(s.due)]


def ttfts(served: list[Served], w: Window) -> list[float]:
    """First token minus due time, for every request due inside the window;
    ``inf`` for one that failed or gave no first token."""
    return [s.token_times[0] - s.due if s.token_times and s.ok is not False
            else math.inf for s in due_in(served, w)]


def queue_waits(served: list[Served], w: Window) -> list[float]:
    """Admission minus due time, for requests due inside the window that
    were admitted."""
    return [s.admitted - s.due for s in due_in(served, w)
            if s.admitted is not None]
