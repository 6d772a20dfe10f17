"""Capture a profiler trace of part of the window and reduce it.

The reduction is the yardstick's: busy time is the union of the intervals
in which a program or an XLA op ran on a device; each call of a jitted step that the
harness annotated on the host is matched to the device execution of its
program, in order; idle gaps are named by the harness span that covered
them on the host.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import shutil

HOST_SPANS = ("bench.submit", "engine.step", "bench.bookkeeping", "bench.idle")
CALL_SPANS = ("bench.decode_step", "bench.prefill_chunk")
WINDOW_SPAN = "bench.traced"


@dataclasses.dataclass
class Interval:
    name: str
    start: float    # seconds, on the trace's clock
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Reduced:
    """What the readers of per-layer metrics get from one trace."""

    window: Interval
    ops: list[Interval]          # device ops, all devices
    modules: list[Interval]      # device program executions
    host: list[Interval]         # the harness's own host spans
    n_devices: int


def start(directory: str) -> None:
    """Start the profiler with the Python tracer off and the host tracer at
    its lowest level, which still records ``TraceAnnotation`` spans.  JAX's
    default traces every Python call, which slows the engine's Python loop
    about twofold, and a trace of that loop would not describe the loop
    that is timed."""
    import jax
    shutil.rmtree(directory, ignore_errors=True)
    os.makedirs(directory)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(directory, profiler_options=options)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def load(directory: str) -> Reduced:
    from jax.profiler import ProfileData

    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {directory}, "
                           f"found {found}")
    return reduce_planes(ProfileData.from_file(found[0]).planes)


def reduce_planes(planes) -> Reduced:
    """Planes as ``ProfileData`` gives them (or any objects with ``name``,
    ``lines``; lines with ``name``, ``events``; events with ``name``,
    ``start_ns``, ``duration_ns``)."""
    ops, modules, host = [], [], []
    n_devices = 0

    def iv(ev):
        return Interval(ev.name, ev.start_ns * 1e-9,
                        (ev.start_ns + ev.duration_ns) * 1e-9)

    for plane in planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            # a chip's trace may hold planes beside its core's (the TPU's
            # non-core activity): a device is a plane that ran programs
            ran = False
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend(iv(e) for e in line.events)
                elif line.name == "XLA Modules":
                    modules.extend(iv(e) for e in line.events)
                    ran = ran or bool(line.events)
            n_devices += ran
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(iv(e) for e in line.events
                            if e.name in HOST_SPANS + CALL_SPANS
                            + (WINDOW_SPAN,))
    windows = [h for h in host if h.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, found "
                           f"{len(windows)}")
    if not ops:
        raise RuntimeError("the trace holds no device op")
    w = windows[0]
    host = sorted((h for h in host if h.name != WINDOW_SPAN),
                  key=lambda h: h.start)
    return Reduced(w, sorted(ops, key=lambda o: o.start),
                   sorted(modules, key=lambda m: m.start), host,
                   max(1, n_devices))


def clip(ivs: list[Interval], w: Interval) -> list[Interval]:
    return [Interval(i.name, max(i.start, w.start), min(i.end, w.end))
            for i in ivs if i.end > w.start and i.start < w.end]


def union(ivs: list[Interval]) -> list[tuple[float, float]]:
    """Disjoint (start, end) pairs covering ``ivs``."""
    out: list[list[float]] = []
    for i in sorted(ivs, key=lambda x: x.start):
        if out and i.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], i.end)
        else:
            out.append([i.start, i.end])
    return [(a, b) for a, b in out]


def busy(r: Reduced, w: Interval | None = None) -> list[tuple[float, float]]:
    """Disjoint intervals inside the window (or ``w``) in which the device
    ran a program or an op.  A program's execution counts whole: inside it
    the chip also waits on its own copies, which the op line does not
    show."""
    return union(clip(r.modules + r.ops, w or r.window))


def busy_seconds(r: Reduced, w: Interval | None = None) -> float:
    """Busy seconds inside the window (or ``w``), averaged over the devices
    (their intervals are pooled, so on one chip it is that chip's busy
    time)."""
    return sum(b - a for a, b in busy(r, w)) / r.n_devices


def idle_gaps(r: Reduced) -> list[tuple[float, float]]:
    spans = busy(r)
    edges = [r.window.start] + [x for s in spans for x in s] + [r.window.end]
    return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def gap_owner(r: Reduced, gap: tuple[float, float]) -> str:
    """The harness span that covered most of an idle gap on the host."""
    best, name = 0.0, "untraced host"
    for h in r.host:
        if h.name not in HOST_SPANS:
            continue
        cover = min(h.end, gap[1]) - max(h.start, gap[0])
        if cover > best:
            best, name = cover, h.name
    return name


def calls(r: Reduced, program: str) -> list[tuple[Interval, Interval]]:
    """(host span, device execution) of every annotated call of the jitted
    step inside the window.  The k-th execution of the step's program on the
    device belongs to the k-th annotated call on the host: one device runs
    its programs in the order they were enqueued, and an execution cannot
    start before its call did.  ``program`` is a substring of the step's
    module name."""
    steps = [m for m in r.modules if program in m.name]
    pairs, j = [], 0
    for h in (h for h in r.host if h.name in CALL_SPANS):
        while j < len(steps) and steps[j].start < h.start:
            j += 1
        if j == len(steps):
            break
        pairs.append((h, steps[j]))
        j += 1
    return [(h, m) for h, m in pairs
            if r.window.start <= h.start and m.end <= r.window.end]


def self_times(ops: list[Interval]) -> dict[str, float]:
    """Seconds each op name ran, less the ops nested inside it (a scan's
    ``while`` op encloses its body's ops on the same line)."""
    total: dict[str, float] = {}
    stack: list[Interval] = []
    for o in sorted(ops, key=lambda o: (o.start, -o.end)):
        while stack and stack[-1].end <= o.start:
            stack.pop()
        total[o.name] = total.get(o.name, 0.0) + o.dur
        if stack:
            parent = stack[-1]
            total[parent.name] -= min(o.end, parent.end) - o.start
        stack.append(o)
    return total


def top_ops(r: Reduced, n: int = 10) -> list[list]:
    total = self_times(clip(r.ops, r.window))
    return [[k[:160], v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def top_gaps(r: Reduced, n: int = 10) -> list[list]:
    gaps = sorted(idle_gaps(r), key=lambda g: g[0] - g[1])[:n]
    return [[gap_owner(r, g), g[1] - g[0]] for g in gaps]
