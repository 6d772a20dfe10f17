"""What a metric reader gets, and how the harness finds the readers.

Each metric of ``BENCHMARK.json`` has a reader ``metrics/<name>.py`` with
``read(r: Reading) -> float | None``.  A metric split by the kind of cell
that reports it (``decode_step_ms.chat``, ``decode_step_ms.long``: the same
quantity, moving the end-to-end metric each kind of cell reports) shares
the reader of its stem, ``metrics/decode_step_ms.py``, unless it has one of
its own.  A reader that finds nothing to read returns None, and the harness
leaves the metric out of the line.
"""
from __future__ import annotations

import bisect
import dataclasses
import importlib.util
import statistics

from bench import devtrace
from bench.spec import BENCH
from bench.stats import Window


@dataclasses.dataclass
class Reading:
    run: object                 # serving.Run after the drain
    cfg: object                 # the program's ModelConfig
    peaks: dict | None          # peaks.json entry of one chip
    setup_s: float
    trace: devtrace.Reduced | None = None
    traced: Window | None = None    # the traced sub-window, host clock
    family: object = None       # the cell's family: its counts

    def served(self):
        return list(self.run.served.values())

    def device_calls(self, kind: str):
        """(harness call record, device execution) of each call of ``kind``
        the trace holds whole.  A call on the trace's host line is the
        harness's record that started at the same moment: the two clocks
        differ by the offset of the ``bench.traced`` span's start."""
        if self.trace is None:
            return []
        offset = self.trace.window.start - self.traced.start
        span = {"decode": "bench.decode_step",
                "prefill": "bench.prefill_chunk"}[kind]
        calls = self.run.calls          # appended in time order
        starts = [c.start for c in calls]
        out = []
        for host, dev in devtrace.calls(self.trace, "decode_step"):
            if host.name != span:
                continue
            t = host.start - offset
            i = bisect.bisect_left(starts, t)
            near = [j for j in (i - 1, i) if 0 <= j < len(calls)]
            j = min(near, key=lambda j: abs(starts[j] - t), default=None)
            if j is not None and abs(starts[j] - t) < 2e-3 \
                    and calls[j].kind == kind:
                out.append((calls[j], dev))
        return out


def reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    if not path.exists():
        path = BENCH / "metrics" / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metric_names(benchmark: dict, cell: str, kind: str) -> list[dict]:
    """The ``kind`` (``end_to_end`` or ``per_layer``) metrics reported in
    ``cell``: those without a ``workloads`` key, and those that list it."""
    return [m for m in benchmark[kind]
            if "workloads" not in m or cell in m["workloads"]]


def trace_cost(run, traced: Window) -> dict:
    """How far the profiler slowed the traced sub-window: the median wall
    time of the engine's decode-only ticks inside it against those of the
    untraced stretch twice its length just before it (the same requests,
    a few seconds earlier), and the output tokens per second of each."""
    starts = [c.start for c in run.calls]

    def decode_only(t0, t1):
        i, j = bisect.bisect_left(starts, t0), bisect.bisect_left(starts, t1)
        return j - i == 1 and run.calls[i].kind == "decode"

    before = Window(max(run.window.start,
                        traced.start - 2 * traced.seconds), traced.start)
    inside, outside = [], []
    for t0, t1 in run.ticks:
        if not decode_only(t0, t1):
            continue
        if traced.holds(t0) and t1 <= traced.end:
            inside.append(t1 - t0)
        elif before.holds(t0) and t1 <= before.end:
            outside.append(t1 - t0)
    tokens = [t for s in run.served.values() for t in s.token_times]
    out = {"traced_tok_s": sum(map(traced.holds, tokens)) / traced.seconds,
           "untraced_tok_s": sum(map(before.holds, tokens)) / before.seconds,
           "traced_ticks": len(inside), "untraced_ticks": len(outside)}
    if inside and outside:
        a, b = statistics.median(inside), statistics.median(outside)
        out.update(traced_tick_ms=1e3 * a, untraced_tick_ms=1e3 * b,
                   slowdown_pct=100.0 * (a / b - 1.0))
    return out
