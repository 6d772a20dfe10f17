"""Capture a profiler trace of part of the window and reduce it.

The reduction is the yardstick's, and keeps each device apart:

- a device's busy time is the union of the intervals in which a program or
  an XLA op ran on it; busy and idle time are averaged over the devices;
- each call of a jitted step that the harness annotated on the host is
  matched, on each device, to that device's execution of its program, in
  order; the call's execution runs from its first start on any device to
  its last end;
- idle gaps are the stretches in which no device ran anything, named by the
  harness span that covered them on the host.

On a trace of one device each of these is that device's own reading.
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
    # the device's plane; "" for a host span
    device: str = dataclasses.field(default="", kw_only=True)

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Execution(Interval):
    """One call's executions of its program, one part per device, spanning
    the first start to the last end."""

    parts: list[Interval] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Reduced:
    """What the readers of per-layer metrics get from one trace."""

    window: Interval
    ops: list[Interval]          # device ops, every device, by start
    modules: list[Interval]      # device program executions, by start
    host: list[Interval]         # the harness's own host spans
    devices: list[str]           # the planes that ran a program

    @property
    def n_devices(self) -> int:
        return len(self.devices)


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
    ops, modules, host, devices = [], [], [], []

    def iv(ev, device=""):
        return Interval(ev.name, ev.start_ns * 1e-9,
                        (ev.start_ns + ev.duration_ns) * 1e-9, device=device)

    for plane in planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            # a chip's trace may hold planes beside its core's (the TPU's
            # non-core activity): a device is a plane that ran programs
            lines = {line.name: line.events for line in plane.lines}
            if lines.get("XLA Modules"):
                devices.append(plane.name)
                ops.extend(iv(e, plane.name)
                           for e in lines.get("XLA Ops", ()))
                modules.extend(iv(e, plane.name)
                               for e in lines["XLA Modules"])
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
                   sorted(modules, key=lambda m: m.start), host, devices)


def clip(ivs: list[Interval], w: Interval) -> list[Interval]:
    return [Interval(i.name, max(i.start, w.start), min(i.end, w.end),
                     device=i.device)
            for i in ivs if i.end > w.start and i.start < w.end]


def on(ivs: list[Interval], device: str | None) -> list[Interval]:
    """The intervals of ``device``; all of them for None."""
    return ivs if device is None else [i for i in ivs if i.device == device]


def union(ivs: list[Interval]) -> list[tuple[float, float]]:
    """Disjoint (start, end) pairs covering ``ivs``."""
    out: list[list[float]] = []
    for i in sorted(ivs, key=lambda x: x.start):
        if out and i.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], i.end)
        else:
            out.append([i.start, i.end])
    return [(a, b) for a, b in out]


def busy(r: Reduced, w: Interval | None = None,
         device: str | None = None) -> list[tuple[float, float]]:
    """Disjoint intervals inside the window (or ``w``) in which ``device``
    (any device, for None) ran a program or an op.  A program's execution
    counts whole: inside it the chip also waits on its own copies, which
    the op line does not show."""
    return union(clip(on(r.modules, device) + on(r.ops, device),
                      w or r.window))


def seconds(spans: list[tuple[float, float]]) -> float:
    return sum(b - a for a, b in spans)


def busy_seconds(r: Reduced, w: Interval | None = None) -> float:
    """Busy seconds inside the window (or ``w``), averaged over the
    devices."""
    return sum(seconds(busy(r, w, d)) for d in r.devices) / r.n_devices


def idle_gaps(r: Reduced) -> list[tuple[float, float]]:
    """The stretches of the window in which no device ran anything."""
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


def calls(r: Reduced, program: str) -> list[tuple[Interval, Execution]]:
    """(host span, device execution) of every annotated call of the jitted
    step inside the window.  On each device the k-th execution of the
    step's program belongs to the k-th annotated call on the host: a device
    runs its programs in the order they were enqueued, and an execution
    cannot start before its call did.  A call counts where every device
    that ran the program holds its execution.  ``program`` is a substring
    of the step's module name."""
    spans = [h for h in r.host if h.name in CALL_SPANS]
    parts: list[list[Interval]] = [[] for _ in spans]
    ran = 0
    for d in r.devices:
        steps = [m for m in on(r.modules, d) if program in m.name]
        ran += bool(steps)
        j = 0
        for k, h in enumerate(spans):
            while j < len(steps) and steps[j].start < h.start:
                j += 1
            if j == len(steps):
                break
            parts[k].append(steps[j])
            j += 1
    out = []
    for h, ps in zip(spans, parts):
        if not ps or len(ps) < ran:
            continue
        x = Execution(ps[0].name, min(p.start for p in ps),
                      max(p.end for p in ps), parts=ps)
        if r.window.start <= h.start and x.end <= r.window.end:
            out.append((h, x))
    return out


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
    """The ops that ran longest, by self time summed over the devices."""
    total: dict[str, float] = {}
    for d in r.devices:
        for k, v in self_times(clip(on(r.ops, d), r.window)).items():
            total[k] = total.get(k, 0.0) + v
    return [[k[:160], v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def top_gaps(r: Reduced, n: int = 10) -> list[list]:
    gaps = sorted(idle_gaps(r), key=lambda g: g[0] - g[1])[:n]
    return [[gap_owner(r, g), g[1] - g[0]] for g in gaps]
