"""Median, over the engine's ticks that ran a decode step and lie whole in
the traced window, of the ms the device sat idle inside the tick's
``serve.step`` span (the program's own span, a profiler annotation while
traced)."""
import statistics

from bench import progtrace


def read(r):
    p = progtrace.of(r)
    idle = progtrace.tick_idle(p, r.trace) if p is not None else []
    return 1e3 * statistics.median(idle) if idle else None
