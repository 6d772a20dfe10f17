"""The trace reduction on a small synthetic trace: busy union, idle share,
calls matched to their device executions, idle gaps named by host span;
each device read apart on four devices; and every reading of the one-device
trace against goldens recorded from the tree before devices were kept
apart."""
import copy
import json
from types import SimpleNamespace as NS

import pytest

from bench import devtrace, spec
from bench.spec import BENCH

MS = 1_000_000   # ns


def ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=int(start_ms * MS),
              duration_ns=int(dur_ms * MS))


def line(name, *events):
    return NS(name=name, events=list(events))


def planes():
    host = NS(name="/host:CPU", lines=[line(
        "python",
        ev(devtrace.WINDOW_SPAN, 0, 100),
        ev("engine.step", 1, 30), ev("bench.decode_step", 2, 1),
        ev("bench.prefill_chunk", 4, 1),
        ev("bench.bookkeeping", 31, 9),       # covers the idle gap 30..40
        ev("engine.step", 40, 50), ev("bench.decode_step", 41, 1),
        ev("bench.idle", 90, 10),
        ev("something.else", 0, 100))])
    dev = NS(name="/device:TPU:0", lines=[
        line("XLA Modules",
             ev("jit_decode_step(1)", -5, 4),     # enqueued before the trace
             ev("jit_decode_step(1)", 3, 10),     # decode call at 2
             ev("jit_write_slot(2)", 13, 2),
             ev("jit_decode_step(1)", 15, 15),    # prefill call at 4
             ev("jit_decode_step(1)", 45, 22)),   # decode call at 41
        line("XLA Ops",
             ev("fusion.1", 3, 5), ev("fusion.2", 6, 7),   # 3..13
             ev("copy", 13, 2), ev("dot", 15, 15),          # 13..30
             ev("dot", 45, 20))])     # 45..65; its module runs to 67
    return [host, dev, NS(name="/device:CPU:0", lines=[
        line("XLA Ops", ev("ignored", 0, 100))])]


def test_reduce_keeps_device_ops_modules_and_harness_spans():
    r = devtrace.reduce_planes(planes())
    assert r.window.dur == pytest.approx(0.1)
    assert r.n_devices == 1
    assert len(r.ops) == 5 and len(r.modules) == 5
    assert "something.else" not in {h.name for h in r.host}


def test_a_plane_that_ran_no_program_is_not_a_device():
    p = planes() + [NS(name="/device:TPU_NON_CORE:0", lines=[
        line("Activity", ev("dma", 0, 100))])]
    r = devtrace.reduce_planes(p)
    assert r.n_devices == 1
    assert devtrace.busy_seconds(r) == pytest.approx(0.049)


def test_busy_union_and_idle_share():
    r = devtrace.reduce_planes(planes())
    # busy: 3..30 and 45..67 (the module outlasts its last op)
    assert devtrace.busy_seconds(r) == pytest.approx(0.049)
    gaps = devtrace.idle_gaps(r)
    assert [(round(a * 1e3), round(b * 1e3)) for a, b in gaps] == \
        [(0, 3), (30, 45), (67, 100)]


def test_calls_pair_host_spans_with_executions_in_order():
    r = devtrace.reduce_planes(planes())
    pairs = devtrace.calls(r, "decode_step")
    got = [(h.name, round(d.start * 1e3), round(d.dur * 1e3))
           for h, d in pairs]
    assert got == [("bench.decode_step", 3, 10),
                   ("bench.prefill_chunk", 15, 15),
                   ("bench.decode_step", 45, 22)]


def test_idle_gaps_are_named_by_the_covering_host_span():
    r = devtrace.reduce_planes(planes())
    top = devtrace.top_gaps(r)
    assert [name for name, _ in top] == \
        ["engine.step", "bench.bookkeeping", "engine.step"]
    assert [s for _, s in top] == pytest.approx([0.033, 0.015, 0.003])
    ops = dict((k, v) for k, v in devtrace.top_ops(r))
    assert ops["dot"] == pytest.approx(0.035)


def test_a_trace_without_the_window_span_is_refused():
    p = planes()
    p[0].lines[0].events = p[0].lines[0].events[1:]
    with pytest.raises(RuntimeError):
        devtrace.reduce_planes(p)


def test_self_time_leaves_out_nested_ops():
    ops = [devtrace.Interval("while", 0.0, 10.0),
           devtrace.Interval("dot", 1.0, 4.0),
           devtrace.Interval("add", 5.0, 6.0),
           devtrace.Interval("dot", 12.0, 13.0)]
    assert devtrace.self_times(ops) == pytest.approx(
        {"while": 6.0, "dot": 4.0, "add": 1.0})


def test_readers_pair_device_executions_with_the_harness_records():
    from bench.readings import Reading, reader
    from bench.serving import Call
    from bench.stats import Window

    r = devtrace.reduce_planes(planes())
    host0 = 500.0      # the harness's clock when the traced span opened
    calls = [Call("decode", host0 + 0.002, contexts=[10, 20]),
             Call("prefill", host0 + 0.004, chunk=(0, 16, False)),
             Call("decode", host0 + 0.041, contexts=[11, 21, 5])]
    counted = {"generated": (100, 130), "decode_steps": (40, 52)}
    reading = Reading(run=NS(calls=calls, counters=counted), cfg=None,
                      peaks=None, setup_s=0, trace=r,
                      traced=Window(host0, host0 + 0.1))
    dec = reading.device_calls("decode")
    assert [(c.contexts, round(d.dur * 1e3)) for c, d in dec] == \
        [([10, 20], 10), ([11, 21, 5], 22)]
    assert [c.chunk for c, _ in reading.device_calls("prefill")] == \
        [(0, 16, False)]
    assert reader("decode_step_ms.long")(reading) == pytest.approx(16.0)
    assert reader("prefill_chunk_ms.chat")(reading) == pytest.approx(15.0)
    assert reader("decode_batch_mean")(reading) == pytest.approx(2.5)
    assert reader("device_idle_pct.chat")(reading) == pytest.approx(51.0)


def test_step_mfu_is_model_flops_over_the_device_busy_time():
    from bench.readings import reader

    reading = qwen3_reading(planes())
    cfg, counters = reading.cfg, reading.family
    flops = counters.decode_step(cfg, [10, 20])[0] \
        + counters.prefill_chunk(cfg, 0, 16, False) \
        + counters.decode_step(cfg, [11, 21, 5])[0]
    # executions span 3..67 ms; the device is busy 3..30 and 45..67 there
    assert reader("step_mfu.chat")(reading) == pytest.approx(
        100 * flops / (0.049 * 197e12))


def test_a_split_metric_reads_through_the_reader_of_its_stem():
    from bench.readings import reader

    assert reader("device_idle_pct.long").__module__ != \
        reader("device_idle_pct.chat").__module__
    assert reader("device_idle_pct.long").__code__.co_filename.endswith(
        "metrics/device_idle_pct.py")
    with pytest.raises(FileNotFoundError):
        reader("no_such_metric.chat")


def test_trace_cost_compares_decode_only_ticks_inside_and_before():
    from bench.readings import trace_cost
    from bench.serving import Call
    from bench.stats import Served, Window

    # ticks of 100 ms before 8 s, of 110 ms after; one prefill tick of
    # 300 ms at 6 s that the comparison leaves out
    ticks, calls, t = [], [], 0.0
    while t < 10.0:
        dur = 0.3 if abs(t - 6.0) < 1e-9 else (0.1 if t < 8.0 else 0.11)
        ticks.append((t, t + dur))
        calls.append(Call("prefill" if dur == 0.3 else "decode", t + 0.001))
        t = round(t + dur, 6)
    served = {0: Served(due=None, sent=0.0,
                        token_times=[b for _, b in ticks])}
    run = NS(ticks=ticks, calls=calls, served=served,
             window=Window(0.0, 10.0))
    cost = trace_cost(run, Window(8.0, 10.0))
    assert cost["traced_tick_ms"] == pytest.approx(110.0)
    assert cost["untraced_tick_ms"] == pytest.approx(100.0)
    assert cost["slowdown_pct"] == pytest.approx(10.0)
    # the untraced stretch is [4 s, 8 s): 20 ticks, the prefill, 17 ticks
    assert cost["traced_ticks"] == 18 and cost["untraced_ticks"] == 37


def test_the_profiler_runs_without_the_python_tracer(tmp_path):
    """A Python loop of many calls leaves a handful of host events, not one
    per call, and the harness's annotations stay in the trace."""
    import glob

    import jax
    from jax.profiler import ProfileData

    def one(i):
        return i + 1

    devtrace.start(str(tmp_path))
    with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
        n = 0
        for _ in range(20000):
            n = one(n)
    devtrace.stop()
    (found,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = [e.name for p in ProfileData.from_file(found).planes
             if p.name.startswith("/host:") for ln in p.lines
             for e in ln.events]
    assert devtrace.WINDOW_SPAN in names
    assert len(names) < 1000


# -- several devices ----------------------------------------------------------

PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def qwen3_reading(p):
    """A reading of the trace ``p`` as the qwen3-8b-l12 cell's, with the
    calls the harness recorded for ``planes()``."""
    from bench.readings import Reading
    from bench.serving import Call
    from bench.stats import Window

    with open(BENCH / "configs" / "qwen3-8b-l12.json") as f:
        config = json.load(f)
    host0 = 500.0      # the harness's clock when the traced span opened
    calls = [Call("decode", host0 + 0.002, contexts=[10, 20]),
             Call("prefill", host0 + 0.004, chunk=(0, 16, False)),
             Call("decode", host0 + 0.041, contexts=[11, 21, 5])]
    counted = {"generated": (100, 130), "decode_steps": (40, 52)}
    return Reading(run=NS(calls=calls, counters=counted),
                   cfg=spec.model_config(config), peaks=PEAKS, setup_s=0,
                   trace=devtrace.reduce_planes(p),
                   traced=Window(host0, host0 + 0.1),
                   family=spec.family(config))


def four(edit=None):
    """``planes()`` with its device on four devices; ``edit(i, plane)``
    changes device ``i``'s copy."""
    host, dev, cpu = planes()
    devs = []
    for i in range(4):
        d = copy.deepcopy(dev)
        d.name = f"/device:TPU:{i}"
        if edit is not None:
            edit(i, d)
        devs.append(d)
    return [host] + devs + [cpu]


READERS = ("decode_step_ms.long", "prefill_chunk_ms.chat",
           "decode_batch_mean", "device_idle_pct.chat", "step_mfu.chat",
           "decode_step_roofline.chat")


def readings(p) -> dict:
    """Every reading of ``p`` that the harness takes from it."""
    from bench.readings import reader

    r = qwen3_reading(p)
    t = r.trace
    out = {"busy_s": devtrace.busy_seconds(t),
           "idle_gaps": devtrace.idle_gaps(t),
           "calls": [[h.name, h.start, h.end, d.start, d.end]
                     for h, d in devtrace.calls(t, "decode_step")],
           "top_ops": devtrace.top_ops(t), "top_gaps": devtrace.top_gaps(t)}
    out.update((m, reader(m)(r)) for m in READERS)
    return out


def test_readings_match_the_parent_tree():
    golden = json.loads((BENCH / "tests" / "data" / "parity.json")
                        .read_text())["trace"]["devtrace"]
    got = json.loads(json.dumps(readings(planes())))    # tuples as lists
    assert got == golden


def test_four_identical_devices_read_as_one():
    one, many = readings(planes()), readings(four())
    assert devtrace.reduce_planes(four()).n_devices == 4
    for k in ("idle_gaps", "calls", "top_gaps"):
        assert many[k] == one[k], k
    for k in ("busy_s", "decode_step_ms.long", "prefill_chunk_ms.chat",
              "decode_batch_mean", "device_idle_pct.chat"):
        assert many[k] == pytest.approx(one[k]), k
    # the same model work over four chips' busy time and peaks
    for k in ("step_mfu.chat", "decode_step_roofline.chat"):
        assert many[k] == pytest.approx(one[k] / 4), k
    assert dict(many["top_ops"]) == pytest.approx(
        {k: 4 * v for k, v in one["top_ops"]})


def test_a_device_that_starts_late_stretches_the_call():
    def late(i, plane):        # the first decode call, 2 ms late on TPU:3
        if i == 3:
            for ln in plane.lines:
                for e in ln.events:
                    if 3 * MS <= e.start_ns < 13 * MS:
                        e.start_ns += 2 * MS

    got = readings(four(late))
    assert [(round(c[3] * 1e3, 6), round(c[4] * 1e3, 6))
            for c in got["calls"]] == [(3, 15), (15, 30), (45, 67)]
    assert got["decode_step_ms.long"] == pytest.approx((12 + 22) / 2)


def test_an_idle_device_shows_in_the_averaged_idle():
    def idle(i, plane):        # TPU:3 runs no commit and no copy at 13 ms
        if i == 3:
            for ln in plane.lines:
                ln.events = [e for e in ln.events if e.start_ns != 13 * MS]

    one, got = readings(planes()), readings(four(idle))
    # busy 49 ms on three devices, 47 on the fourth
    assert got["busy_s"] == pytest.approx((3 * 0.049 + 0.047) / 4)
    assert got["device_idle_pct.chat"] == pytest.approx(
        100 * (1 - (3 * 0.049 + 0.047) / 4 / 0.1))
    assert got["device_idle_pct.chat"] > one["device_idle_pct.chat"]
    # no new gap: the others ran while TPU:3 sat idle
    assert got["idle_gaps"] == one["idle_gaps"]


def test_readers_reach_the_counts_through_the_family():
    from bench.readings import reader

    r = qwen3_reading(planes())
    r.family = NS(decode_step=lambda cfg, contexts: (1e12, 0.0),
                  prefill_chunk=lambda cfg, *chunk: 1e12)
    # three calls of 1 TFLOP over 49 ms busy; two decode calls, 32 ms
    assert reader("step_mfu.chat")(r) == pytest.approx(
        100 * 3e12 / (0.049 * 197e12))
    assert reader("decode_step_roofline.chat")(r) == pytest.approx(
        100 * 2e12 / 197e12 / 0.032)
