"""The program's spans and scopes in a trace, on a small synthetic trace:
innermost-scope attribution (an op outside every scope, ops XLA left
without a name inside the layer scan), the idle time inside the engine's
ticks and the span that held the host, and the readers of the per-layer
metrics; each device read apart on four devices; every reading of the
one-device trace against goldens recorded from the tree before devices
were kept apart; the wire decoder on a serialized XSpace."""
import copy
import json
from types import SimpleNamespace as NS

import pytest

from bench import devtrace, progtrace
from bench.spec import BENCH

MS = 1_000_000   # ns
BODY = "jit(decode_step_batch)/layers/while/body/closed_call"


def ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=int(start_ms * MS),
              duration_ns=int(dur_ms * MS))


def line(name, *events):
    return NS(name=name, events=list(events))


def harness_planes(n: int = 1) -> list:
    """The planes devtrace reads: the window, the harness's call spans,
    ``n`` devices' program executions and ops."""
    host = NS(name="/host:CPU", lines=[line(
        "python",
        ev(devtrace.WINDOW_SPAN, 0, 100),
        ev("bench.decode_step", 17, 1),
        ev("bench.prefill_chunk", 41, 1),
        ev("bench.decode_step", 62, 0.3))])
    return [host] + [NS(name=f"/device:TPU:{i}", lines=[
        line("XLA Modules",
             ev("jit_decode_step_batch(1)", 18, 20),
             ev("jit_decode_step_prefill(2)", 42, 8),
             ev("jit_decode_step_batch(1)", 62.5, 25.5)),
        line("XLA Ops", ev("fusion", 18, 20), ev("fusion", 42, 8),
             ev("fusion", 62.5, 25.5))]) for i in range(n)]


def harness_trace() -> devtrace.Reduced:
    return devtrace.reduce_planes(harness_planes())


def span(name, a, b, **args):
    return progtrace.Span(name, a * 1e-3, b * 1e-3, args)


SPANS = [
    # a decode tick: idle 10..18 and 38..40 (10 ms)
    span("serve.step", 10, 40), span("serve.admit", 10, 12),
    span("serve.prefill_tick", 12, 14), span("serve.decode_tick", 14, 40),
    span("serve.grow", 14, 15), span("serve.decode_inputs", 15, 17),
    span("serve.decode_step", 17, 39, batch=2),
    span("serve.decode_sync", 18, 39), span("serve.retire", 39, 40),
    # a tick that ran no decode step: left out
    span("serve.step", 40, 60), span("serve.prefill_tick", 40, 59),
    span("serve.prefill_chunk", 41, 42, rid=7, tokens=16),
    span("serve.decode_tick", 59, 60),
    # a decode tick: idle 60..62.5 and 88..90 (4.5 ms)
    span("serve.step", 60, 90), span("serve.admit", 61, 62),
    span("serve.decode_tick", 62, 90),
    span("serve.decode_step", 62, 89, batch=3),
    span("serve.decode_sync", 63, 89), span("serve.retire", 89, 90),
    # runs past the traced window's end: left out
    span("serve.step", 95, 105), span("serve.decode_tick", 95, 105),
    span("serve.decode_step", 96, 104, batch=3),
]


def op(name, start_ms, dur_ms, op_name=""):
    return (start_ms * MS, dur_ms * MS, name, op_name)


OPS = [
    # first decode call, 18..38
    op("gather", 18, 1, "jit(decode_step_batch)/embed/jit(_take)/gather:"),
    op("while.4", 19, 16),                        # no op_name on the v5e
    op("dynamic-slice", 19, 1,
       "jit(decode_step_batch)/layers/while/body/dynamic_slice:"),
    op("copy.1", 20, 2),                          # added by XLA
    op("fusion.1", 22, 4, f"{BODY}/attention/dot_general:"),
    op("fusion.2", 26, 4, f"{BODY}/attention/kv_write/scatter:"),
    op("fusion.3", 30, 4, f"{BODY}/moe/dot_general:"),
    op("fusion.4", 35, 2, "jit(decode_step_batch)/lm_head/dot_general:"),
    op("copy-done", 37, 1),                       # outside every scope
    # the prefill chunk, 42..50: not a decode call
    op("fusion.9", 42, 8, f"{BODY}/attention/kv_write/scatter:"),
    # second decode call, 62.5..88
    op("fusion.1", 63, 10, f"{BODY}/attention/dot_general:"),
    op("fusion.2", 73, 10, f"{BODY}/attention/kv_write/scatter:"),
]


def profile() -> progtrace.Profile:
    return progtrace.reduce(SPANS, [{"name": "/device:TPU:0", "ops": OPS}])


@pytest.mark.parametrize("op_name,scope", [
    (f"{BODY}/attention/kv_write/scatter:", "kv_write"),
    (f"{BODY}/attention/jit(_where)/select_n:", "attention"),
    ("jit(decode_step_batch)/lm_head/dot_general:", "lm_head"),
    ("broadcast_in_dim;attention/broadcast_in_dim", "attention"),
    ("params['embed']['table']:", None),
    ("", None),
])
def test_innermost_scope_of_an_op_name(op_name, scope):
    assert progtrace.innermost(op_name) == scope


def test_ops_without_a_name_take_the_scope_that_encloses_them():
    scopes = {o.name: o.scope for o in profile().ops}
    assert scopes["while.4"] == "layers"          # from its body's paths
    assert scopes["copy.1"] == "layers"           # inside the loop
    assert scopes["dynamic-slice"] == "layers"
    assert scopes["copy-done"] is None            # outside every scope
    assert scopes["gather"] == "embed" and scopes["fusion.3"] == "moe"


def test_scope_seconds_counts_self_time():
    p = profile()
    first = devtrace.Interval("jit_decode_step_batch(1)", 0.018, 0.038,
                              device="/device:TPU:0")
    by = {k: round(v * 1e3, 6) for k, v in
          progtrace.scope_seconds(p, [first]).items()}
    # the loop's own 1 ms (16 less its children's 15) is bare `layers`
    assert by == {"embed": 1, "layers": 4, "attention": 4, "kv_write": 4,
                  "moe": 4, "lm_head": 2, None: 1}


def test_tick_idle_keeps_whole_decode_ticks_inside_the_window():
    p, trace = profile(), harness_trace()
    assert [(round(t.start * 1e3), round(t.end * 1e3))
            for t in progtrace.ticks(p, trace.window)] == [(10, 40),
                                                           (60, 90)]
    assert [round(x * 1e3, 6) for x in progtrace.tick_idle(p, trace)] == \
        [10.0, 4.5]


def test_idle_is_put_down_to_the_innermost_span():
    by = progtrace.idle_by_span(profile(), harness_trace())
    assert {k: round(v * 1e3, 6) for k, v in by.items()} == {
        "serve.step": 1, "serve.admit": 3, "serve.prefill_tick": 2,
        "serve.grow": 1, "serve.decode_inputs": 2, "serve.decode_step": 1.5,
        "serve.decode_sync": 2, "serve.retire": 2}


def reading(p):
    from bench.readings import Reading
    from bench.serving import Call
    from bench.stats import Window

    host0 = 500.0      # the harness's clock when the traced span opened
    calls = [Call("decode", host0 + 0.017, contexts=[10, 20]),
             Call("prefill", host0 + 0.041, chunk=(0, 16, False)),
             Call("decode", host0 + 0.062, contexts=[11, 21, 5])]
    r = Reading(run=NS(calls=calls, cell=NS(name="toy")), cfg=None,
                peaks=None, setup_s=0, trace=harness_trace(),
                traced=Window(host0, host0 + 0.1))
    r._profile = p
    return r


def test_readers_of_the_program_metrics():
    from bench.readings import reader

    r = reading(profile())
    assert reader("tick_idle_ms.chat")(r) == pytest.approx(7.25)
    assert reader("tick_idle_ms.long")(r) == pytest.approx(7.25)
    # per decode call; the prefill chunk's scatter is not a decode call's
    assert reader("kv_write_ms.chat")(r) == pytest.approx(7.0)
    assert reader("attention_ms.long")(r) == pytest.approx(7.0)
    assert reader("moe_ms.chat")(r) == pytest.approx(2.0)


def test_readers_find_nothing_in_a_program_without_spans_or_scopes():
    from bench.readings import reader

    bare = [(t, d, name, "") for t, d, name, _ in OPS]
    r = reading(progtrace.reduce([], [{"name": "/device:TPU:0",
                                       "ops": bare}]))
    for name in ("tick_idle_ms.chat", "kv_write_ms.chat",
                 "attention_ms.long", "moe_ms.chat"):
        assert reader(name)(r) is None, name
    untraced = reading(profile())
    untraced.trace = None
    assert reader("kv_write_ms.chat")(untraced) is None
    assert reader("tick_idle_ms.chat")(untraced) is None


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 9000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 2000000
             stats { metadata_id: 12 int64_value: 7 } }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1500000 }
    events { metadata_id: 4 offset_ps: 5000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.2 = bf16[8] f()"
      display_name: "fusion.2" stats { metadata_id: 10
      str_value: "jit(f)/layers/while/body/attention/kv_write/scatter:" } } }
  event_metadata { key: 2 value { id: 2 name: "copy.1"
      stats { metadata_id: 10 ref_value: 11 } } }
  event_metadata { key: 3 value { id: 3 name: "jit_decode_step_batch(1)" } }
  event_metadata { key: 4 value { id: 4 name: "copy-done" } }
  stat_metadata { key: 10 value { id: 10 name: "tf_op" } }
  stat_metadata { key: 11 value { id: 11 name: "jit(f)/mlp/dot_general" } }
  stat_metadata { key: 12 value { id: 12 name: "run_id" } }
}
planes { id: 2 name: "/device:CPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "ignored" } }
}
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "serve.step" } }
}
"""


def test_the_wire_decoder_reads_each_ops_tf_op():
    from jax.profiler import ProfileData

    data = ProfileData.text_proto_to_serialized_xspace(XSPACE)
    planes = progtrace.xspace(data)
    assert [p["name"] for p in planes] == ["/device:TPU:0"]
    assert planes[0]["ops"] == [
        (1500.0, 2000.0, "fusion.2",
         "jit(f)/layers/while/body/attention/kv_write/scatter:"),
        (4000.0, 1500.0, "copy.1", "jit(f)/mlp/dot_general"),
        (6000.0, 1000.0, "copy-done", "")]
    # the same clock as ProfileData's events
    (tpu,) = [p for p in ProfileData.from_serialized_xspace(data).planes
              if p.name == "/device:TPU:0"]
    (ops,) = [ln for ln in tpu.lines if ln.name == "XLA Ops"]
    assert [(e.start_ns, e.duration_ns) for e in ops.events] == \
        [o[:2] for o in planes[0]["ops"]]
    scopes = [o.scope for o in progtrace.reduce([], planes).ops]
    assert scopes == ["kv_write", "mlp", None]


# -- several devices, and the parent's readings -------------------------------

def read_all(p: progtrace.Profile, trace: devtrace.Reduced) -> dict:
    """Every reading of a profile and its trace that the harness takes."""
    from bench.readings import reader
    from bench.spec import model_config, family

    r = reading(p)
    r.trace = trace
    with open(BENCH / "configs" / "qwen3-8b-l12.json") as f:
        config = json.load(f)
    r.cfg, r.family = model_config(config), family(config)
    r.peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    first = [m for m in trace.modules if round(m.start, 9) == 0.018]
    out = {"busy_s": devtrace.busy_seconds(trace),
           "idle_gaps": devtrace.idle_gaps(trace),
           "calls": [[h.name, h.start, h.end, d.start, d.end]
                     for h, d in devtrace.calls(trace, "decode_step")],
           "top_ops": devtrace.top_ops(trace),
           "top_gaps": devtrace.top_gaps(trace),
           "scope_seconds": {str(k): v for k, v in
                             progtrace.scope_seconds(p, first).items()},
           "ticks": [[x.start, x.end]
                     for x in progtrace.ticks(p, trace.window)],
           "tick_idle": progtrace.tick_idle(p, trace),
           "idle_by_span": progtrace.idle_by_span(p, trace)}
    for m in ("tick_idle_ms.chat", "kv_write_ms.chat", "attention_ms.long",
              "moe_ms.chat", "decode_step_ms.chat", "prefill_chunk_ms.chat",
              "device_idle_pct.long", "step_mfu.long",
              "decode_step_roofline.long"):
        out[m] = reader(m)(r)
    return json.loads(json.dumps(out))      # tuples as lists


def test_readings_match_the_parent_tree():
    golden = json.loads((BENCH / "tests" / "data" / "parity.json")
                        .read_text())["trace"]["progtrace"]
    assert read_all(profile(), harness_trace()) == golden


def four_devices(trim_last: float = 0.0, shift_last: float = 0.0):
    """``profile()`` and ``harness_trace()`` on four devices; TPU:3's last
    decode call ends ``trim_last`` ms early, or runs ``shift_last`` ms
    late."""
    planes = harness_planes(4)
    ops = [[list(o) for o in OPS] for _ in range(4)]
    for ln in planes[4].lines:
        ln.events[-1].duration_ns -= int(trim_last * MS)
        ln.events[-1].start_ns += int(shift_last * MS)
    ops[3][-1][1] -= trim_last * MS
    for o in ops[3][-2:]:
        o[0] += shift_last * MS
    p = progtrace.reduce(copy.deepcopy(SPANS), [
        {"name": f"/device:TPU:{i}", "ops": [tuple(o) for o in ops[i]]}
        for i in range(4)])
    return p, devtrace.reduce_planes(planes)


def test_four_identical_devices_read_as_one():
    one, many = read_all(profile(), harness_trace()), read_all(*four_devices())
    for k in ("idle_gaps", "calls", "ticks"):
        assert many[k] == one[k], k
    for k in ("busy_s", "tick_idle", "tick_idle_ms.chat", "kv_write_ms.chat",
              "attention_ms.long", "moe_ms.chat", "decode_step_ms.chat",
              "device_idle_pct.long"):
        assert many[k] == pytest.approx(one[k]), k
    assert many["idle_by_span"] == pytest.approx(one["idle_by_span"])
    assert many["scope_seconds"] == pytest.approx(one["scope_seconds"])
    assert many["step_mfu.long"] == pytest.approx(one["step_mfu.long"] / 4)


def test_an_idle_device_shows_in_the_averaged_tick_idle():
    # TPU:3's second decode call ends at 80 ms, not 88: 8 ms more idle in
    # the tick of 60..90 on that device, its kv_write 8 ms shorter
    one = read_all(profile(), harness_trace())
    got = read_all(*four_devices(trim_last=8.0))
    assert got["tick_idle"] == pytest.approx([0.010, 0.0045 + 0.008 / 4])
    assert got["idle_by_span"]["serve.retire"] == pytest.approx(
        one["idle_by_span"]["serve.retire"])
    assert sum(got["idle_by_span"].values()) == pytest.approx(
        sum(one["idle_by_span"].values()) + 0.008 / 4)
    # the call still runs to 88 ms, where the other devices end it
    assert got["calls"] == one["calls"]
    assert got["kv_write_ms.chat"] == pytest.approx((4 + (3 * 10 + 2) / 4)
                                                    / 2)


def test_a_device_that_starts_late_stretches_the_call():
    # TPU:3 runs the second decode call 62.5..88 two ms late, to 90 ms
    one = read_all(profile(), harness_trace())
    got = read_all(*four_devices(shift_last=2.0))
    assert [round(c[4] * 1e3, 6) for c in got["calls"]] == [38, 50, 90]
    assert got["decode_step_ms.chat"] == pytest.approx(
        one["decode_step_ms.chat"] + 1.0)
    # its ops move with it: the same time by scope
    assert got["kv_write_ms.chat"] == pytest.approx(one["kv_write_ms.chat"])
