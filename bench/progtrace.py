"""The program's own spans and scopes in a traced run's profile.

``devtrace`` keeps what the harness annotated.  This module re-reads the
same ``.xplane.pb`` for what the program put there itself:

- the paged engine's host spans (``serve.*``): ``repro.obs`` spans, which
  become profiler annotations while a profiler session records, with the
  args they carry (``rid``, ``batch``, ...);
- every device op with the innermost named scope of the model step
  (``SCOPES``, from ``models/transformer.py`` and ``models/layers.py``)
  that it came from.  A v5e trace gives each op's event metadata the op's
  HLO ``op_name`` (the ``jax.named_scope`` path) as its ``tf_op`` stat.
  ``ProfileData`` does not expose event metadata, so the device planes are
  read from the file's protobuf wire format directly (``xspace``).

    python3 -m bench.progtrace .bench_out/trace/<cell>

prints, for a traced run, the idle time inside the engine's ticks by the
span that held the host, and the batched decode program's device time by
scope.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import statistics
import sys

from bench import devtrace
from bench.spec import REPO

# where bench/run.py writes a traced run's profile, one directory per cell
TRACE_DIR = REPO / ".bench_out" / "trace"
SCOPES = ("layers", "attention", "kv_write", "moe", "mlp", "lm_head", "embed")
SPAN_PREFIX = "serve."
TICK = "serve.step"
DECODE = "serve.decode_step"
DECODE_PROGRAM = "decode_step_batch"


@dataclasses.dataclass
class Span(devtrace.Interval):
    args: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Op(devtrace.Interval):
    scope: str | None = None     # innermost of SCOPES, None outside all


@dataclasses.dataclass
class Profile:
    spans: list[Span]            # the program's host spans, by start
    ops: list[Op]                # device ops, all devices, by start


def innermost(op_name: str) -> str | None:
    """The innermost of ``SCOPES`` on an op's ``op_name`` path.  A fused
    op's name may join its sources' paths with ``;``: the first path that
    holds a scope decides."""
    for path in op_name.split(";"):
        found = [p for p in path.rstrip(":").split("/") if p in SCOPES]
        if found:
            return found[-1]
    return None


# -- the file -----------------------------------------------------------------

def _varint(b, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        c = b[i]
        i += 1
        value |= (c & 0x7F) << shift
        if c < 0x80:
            return value, i
        shift += 7


def _fields(b, i: int = 0, end: int | None = None):
    """(field number, value) of one protobuf message: an int for a varint,
    a ``(start, end)`` slice of ``b`` for a length-delimited field."""
    end = len(b) if end is None else end
    while i < end:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(b, i)
        elif kind == 2:
            n, i = _varint(b, i)
            value = (i, i + n)
            i += n
        elif kind == 1:
            value, i = None, i + 8
        elif kind == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, value


def _event(b, i: int, end: int) -> tuple[int, int, int]:
    """``metadata_id``, ``offset_ps``, ``duration_ps`` of one XEvent: the
    trace's bulk, so read without building the field dict."""
    md = offset = duration = 0
    while i < end:
        key = b[i]
        kind = key & 7
        if key >= 0x80 or kind not in (0, 2):
            rest = dict(_fields(b, i, end))
            return (rest.get(1, md), rest.get(2, offset),
                    rest.get(3, duration))
        value, i = _varint(b, i + 1)
        if kind == 2:                                 # .stats: skipped
            i += value
        elif key == 0x08:                             # .metadata_id
            md = value
        elif key == 0x10:                             # .offset_ps
            offset = value
        elif key == 0x18:                             # .duration_ps
            duration = value
    return md, offset, duration


def _text(b, at) -> str:
    return bytes(b[at[0]:at[1]]).decode("utf-8", "replace")


def xspace(data: bytes) -> list[dict]:
    """The device planes of a serialized XSpace: for each, its name and the
    events of its ``XLA Ops`` line as ``(start_ns, duration_ns, name,
    tf_op)``.
    Field numbers are those of ``tsl/profiler/protobuf/xplane.proto``."""
    b = memoryview(data)
    out = []
    for f, plane in _fields(b):                       # XSpace.planes
        if f != 1:
            continue
        name, lines, metadata, stat_names = "", [], [], {}
        for pf, v in _fields(b, *plane):
            if pf == 2:                               # XPlane.name
                name = _text(b, v)
            elif pf == 3:                             # XPlane.lines
                lines.append(v)
            elif pf == 4:                             # XPlane.event_metadata
                metadata.append(v)
            elif pf == 5:                             # XPlane.stat_metadata
                entry = dict(_fields(b, *v))
                stat = dict(_fields(b, *entry[2]))
                stat_names[entry[1]] = _text(b, stat[2]) if 2 in stat else ""
        if not name.startswith("/device:") or "CPU" in name:
            continue
        ops = {}                                      # id -> (name, tf_op)
        for entry in metadata:
            entry = dict(_fields(b, *entry))
            op = tf_op = ""
            for mf, v in _fields(b, *entry[2]):      # XEventMetadata
                if mf == 4 or (mf == 2 and not op):   # .display_name, .name
                    op = _text(b, v)
                if mf != 5:                           # .stats
                    continue
                stat = dict(_fields(b, *v))
                if stat_names.get(stat.get(1)) != "tf_op":
                    continue
                if 5 in stat:                         # XStat.str_value
                    tf_op = _text(b, stat[5])
                elif 7 in stat:                       # XStat.ref_value
                    tf_op = stat_names.get(stat[7], "")
            ops[entry[1]] = (op, tf_op)
        events = []
        for line in lines:
            fl = dict(_fields(b, *line))
            if 2 not in fl or _text(b, fl[2]) != "XLA Ops":
                continue
            t0 = fl.get(3, 0)                         # XLine.timestamp_ns
            for lf, v in _fields(b, *line):
                if lf != 4:                           # XLine.events
                    continue
                md, offset_ps, duration_ps = _event(b, *v)
                events.append((t0 + offset_ps * 1e-3, duration_ps * 1e-3)
                              + ops.get(md, ("", "")))
        out.append({"name": name, "ops": events})
    return out


def _host_spans(path: str) -> list[Span]:
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans.extend(Span(e.name, e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9,
                              dict(e.stats))
                         for e in line.events
                         if e.name.startswith(SPAN_PREFIX))
    return spans


def scoped(events: list[tuple]) -> list[Op]:
    """The ops of one device's ``XLA Ops`` line, each with its scope: that
    of its own ``op_name``, else that of the op enclosing it on the line.
    On the v5e the layer scan's ``while`` op and the copies XLA adds carry
    no ``op_name``; a loop takes the scope of the path its body's ops name
    before ``/while/body``."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    parent, stack = [], []
    for t, d, _, _ in events:
        while stack and sum(events[stack[-1]][:2]) <= t:
            stack.pop()
        parent.append(stack[-1] if stack else -1)
        stack.append(len(parent) - 1)
    scope = [innermost(e[3]) for e in events]
    for k, e in enumerate(events):
        up = parent[k]
        if up >= 0 and scope[up] is None and "/while/body" in e[3]:
            scope[up] = innermost(e[3].split("/while/body")[0])
    for k, up in enumerate(parent):     # a parent comes before its children
        if scope[k] is None and up >= 0:
            scope[k] = scope[up]
    return [Op(name, t * 1e-9, (t + d) * 1e-9, scope[k])
            for k, (t, d, name, _) in enumerate(events)]


def reduce(spans: list[Span], planes: list[dict]) -> Profile:
    ops = [o for p in planes for o in scoped(p["ops"])]
    return Profile(sorted(spans, key=lambda s: s.start),
                   sorted(ops, key=lambda o: o.start))


def load(directory) -> Profile:
    found = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {directory}, "
                           f"found {found}")
    with open(found[0], "rb") as f:
        planes = xspace(f.read())
    return reduce(_host_spans(found[0]), planes)


def of(r) -> Profile | None:
    """The profile of a reading's traced run, read once per reading; None
    without a trace."""
    if r.trace is None:
        return None
    if getattr(r, "_profile", None) is None:
        r._profile = load(TRACE_DIR / r.run.cell.name)
    return r._profile


# -- reductions ------------------------------------------------------------

def _covered(busy: list[tuple[float, float]], a: float, b: float) -> float:
    """Seconds of [a, b] that the disjoint, sorted ``busy`` intervals
    cover."""
    i = max(0, bisect.bisect_right(busy, (a, float("inf"))) - 1)
    total = 0.0
    while i < len(busy) and busy[i][0] < b:
        total += max(0.0, min(b, busy[i][1]) - max(a, busy[i][0]))
        i += 1
    return total


def ticks(p: Profile, window: devtrace.Interval) -> list[Span]:
    """The engine's ticks that ran a decode step and lie whole in the
    traced window."""
    steps = [s for s in p.spans if s.name == TICK
             and window.start <= s.start and s.end <= window.end]
    starts = [s.start for s in p.spans]
    out = []
    for t in steps:
        i = bisect.bisect_left(starts, t.start)
        j = bisect.bisect_right(starts, t.end)
        if any(s.name == DECODE and s.end <= t.end for s in p.spans[i:j]):
            out.append(t)
    return out


def tick_idle(p: Profile, trace: devtrace.Reduced) -> list[float]:
    """Seconds the device sat idle inside each of ``ticks``."""
    busy = devtrace.busy(trace)
    return [t.dur - _covered(busy, t.start, t.end)
            for t in ticks(p, trace.window)]


def idle_by_span(p: Profile, trace: devtrace.Reduced) -> dict[str, float]:
    """The device's idle seconds inside ``ticks``, each piece put down to
    the innermost span that held the host then (``serve.step`` for its
    own self time)."""
    busy = devtrace.busy(trace)
    starts = [s.start for s in p.spans]
    out: dict[str, float] = {}
    for t in ticks(p, trace.window):
        i = bisect.bisect_left(starts, t.start)
        j = bisect.bisect_right(starts, t.end)
        inner = [s for s in p.spans[i:j] if s.end <= t.end]
        cuts = sorted({x for s in inner for x in (s.start, s.end)})
        for a, b in zip(cuts, cuts[1:]):
            idle = (b - a) - _covered(busy, a, b)
            if idle <= 0:
                continue
            owner = _innermost(inner, 0.5 * (a + b)).name
            out[owner] = out.get(owner, 0.0) + idle
    return out


def _innermost(spans: list[Span], t: float) -> Span | None:
    """The innermost of ``spans`` that covers ``t``: spans on one thread
    nest, so the covering span that started last, and of those the one
    that ends first."""
    return max((s for s in spans if s.start <= t < s.end),
               key=lambda s: (s.start, -s.end), default=None)


def scope_seconds(p: Profile, executions: list[devtrace.Interval]
                  ) -> dict[str | None, float]:
    """Device seconds, by innermost scope (None: outside every scope), of
    the ops that ran inside ``executions`` of one program (whose op names
    are unique), each op less the ops nested in it."""
    starts = [o.start for o in p.ops]
    ops = []
    for x in executions:
        i = bisect.bisect_left(starts, x.start)
        j = bisect.bisect_right(starts, x.end)
        ops.extend(o for o in p.ops[i:j] if o.end <= x.end)
    scope = {o.name: o.scope for o in ops}
    out: dict[str | None, float] = {}
    for name, t in devtrace.self_times(ops).items():
        out[scope[name]] = out.get(scope[name], 0.0) + t
    return out


def decode_scope_ms(r, scope: str) -> float | None:
    """Device ms per traced decode call of the ops whose innermost scope
    is ``scope``; None where no op of those calls carries it (a program
    without the scope)."""
    p = of(r)
    calls = r.device_calls("decode")
    if p is None or not calls:
        return None
    by = scope_seconds(p, [d for _, d in calls])
    return 1e3 * by[scope] / len(calls) if scope in by else None


def summary(directory) -> dict:
    """What PERF.md reads from one traced run: the decode ticks' idle time
    by span, and the batched decode program's device time by scope."""
    trace = devtrace.load(str(directory))
    p = load(directory)
    idle = tick_idle(p, trace)
    calls = [m for m in devtrace.clip(trace.modules, trace.window)
             if DECODE_PROGRAM in m.name and m.start > trace.window.start
             and m.end < trace.window.end]
    by = scope_seconds(p, calls)
    op_s = sum(by.values())
    return {
        "ticks": len(idle),
        "tick_ms_median": 1e3 * statistics.median(
            t.dur for t in ticks(p, trace.window)) if idle else None,
        "tick_idle_ms_median": 1e3 * statistics.median(idle) if idle
        else None,
        "idle_ms_by_span": {k: 1e3 * v for k, v in sorted(
            idle_by_span(p, trace).items(), key=lambda kv: -kv[1])},
        "decode_calls": len(calls),
        "decode_call_ms": 1e3 * sum(m.dur for m in calls) / len(calls)
        if calls else None,
        "decode_ms_by_scope": {str(k): 1e3 * v / len(calls) for k, v in
                               sorted(by.items(), key=lambda kv: -kv[1])}
        if calls else {},
        "decode_scoped_share": 1.0 - by.get(None, 0.0) / op_s if op_s
        else None,
        # the window's longest idle gaps and the span that held the host
        "top_gaps_ms": [
            [getattr(_innermost(p.spans, 0.5 * (a + b)), "name", None),
             1e3 * (b - a)]
            for a, b in sorted(devtrace.idle_gaps(trace),
                               key=lambda g: g[0] - g[1])[:5]],
    }


if __name__ == "__main__":
    print(json.dumps(summary(sys.argv[1]), indent=1))
