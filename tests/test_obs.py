"""The observability layer (DESIGN.md §13).

Layers under test:

  * tracer mechanics — span nesting/ordering/depth, ring wraparound with a
    correct dropped count, Chrome trace-event export shape;
  * metrics mechanics — counter/gauge basics, histogram bucket-edge
    semantics (half-open buckets, edge values open their bucket, quantiles
    clamped to observed min/max);
  * the disabled-mode contract — obs off records zero events and creates
    zero registry entries across a full serve run;
  * the non-interference gate — a traced PagedServeEngine run produces
    BIT-IDENTICAL outputs to an untraced one, and its trace replays every
    request lifecycle in order;
  * export/validation round-trip — telemetry documents validate, corrupt
    ones are rejected with specific defects;
  * satellites — serve stats latency summaries match np.percentile,
    measurement failures carry elapsed_s + error_type into the tuning DB,
    CalibratedCostModel forwards its attached cache's hit/miss counts.
"""
import functools
import json
import math

import numpy as np
import pytest

from repro import obs
from repro.obs.export import (snapshot, validate_telemetry,
                              validate_telemetry_file)
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               geometric_edges, linear_edges)
from repro.obs.trace import ARGS, DEPTH, DUR, NAME, PH, TS, Tracer

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # container ships without hypothesis
    HAVE_HYPOTHESIS = False


@pytest.fixture(autouse=True)
def _obs_off():
    """Every test starts and ends with observability disabled — the module
    singleton must never leak across tests."""
    obs.disable()
    yield
    obs.disable()


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------

def test_span_nesting_depth_and_order():
    tr = Tracer(64)
    with tr.span("outer"):
        with tr.span("inner"):
            tr.instant("tick", {"i": 1})
    evs = tr.events()
    # completion order: the instant fires first, then inner closes, then
    # outer — but depths record the *nesting* structure
    assert [(e[NAME], e[PH], e[DEPTH]) for e in evs] == [
        ("tick", "i", 2), ("inner", "X", 1), ("outer", "X", 0)]
    inner, outer = evs[1], evs[2]
    assert outer[TS] <= inner[TS]                    # outer opened first
    assert outer[DUR] >= inner[DUR]                  # and covers inner
    assert inner[TS] + inner[DUR] <= outer[TS] + outer[DUR] + 1e-6


def test_span_args_recorded():
    tr = Tracer(8)
    with tr.span("s", {"k": 3}):
        pass
    assert tr.events()[0][ARGS] == {"k": 3}


def test_ring_wraparound_keeps_newest_and_counts_dropped():
    tr = Tracer(8)
    for i in range(20):
        tr.instant("e", {"i": i})
    assert len(tr) == 8
    assert tr.recorded == 20
    assert tr.dropped == 12
    assert [e[ARGS]["i"] for e in tr.events()] == list(range(12, 20))


def test_chrome_export_schema_and_serializability():
    tr = Tracer(16)
    with tr.span("work", {"n": 2}):
        tr.instant("mark")
    doc = tr.to_chrome()
    json.dumps(doc)                                    # must serialize
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert {e["name"] for e in evs} == {"work", "mark"}
    for e in evs:
        assert set(e) >= {"name", "ph", "ts", "pid", "tid", "args"}
        assert e["ph"] in ("X", "i")
        assert ("dur" in e) == (e["ph"] == "X")
        assert "depth" in e["args"]


def test_tracer_rejects_zero_capacity():
    with pytest.raises(ValueError, match="capacity"):
        Tracer(0)


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------

def test_counter_and_gauge_basics():
    c = Counter()
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    g = Gauge()
    for v in (4.0, -1.0, 2.0):
        g.set(v)
    assert (g.value, g.min, g.max, g.n_sets) == (2.0, -1.0, 4.0, 3)


def test_histogram_bucket_edges_are_half_open():
    h = Histogram([1.0, 2.0, 4.0])
    for v in (0.5, 1.0, 1.5, 2.0, 3.999, 4.0, 100.0):
        h.observe(v)
    # buckets: (-inf,1) [1,2) [2,4) [4,inf)
    assert h.counts == [1, 2, 2, 2]
    assert h.count == 7 and sum(h.counts) == h.count
    assert h.min == 0.5 and h.max == 100.0


def test_histogram_quantiles_clamped_and_monotone():
    h = Histogram(geometric_edges(1e-3, 10.0))
    vals = [0.01, 0.02, 0.05, 0.1, 0.5, 1.0, 2.0]
    for v in vals:
        h.observe(v)
    qs = [h.quantile(q) for q in (0.0, 0.25, 0.5, 0.75, 0.95, 1.0)]
    assert all(min(vals) <= q <= max(vals) for q in qs)
    assert qs == sorted(qs)
    assert math.isclose(h.mean, sum(vals) / len(vals))


def test_histogram_single_value_degenerate():
    h = Histogram([1.0, 2.0])
    h.observe(1.5)
    assert h.quantile(0.5) == 1.5 == h.quantile(0.99)


def test_edge_builders_validate():
    assert geometric_edges(1.0, 8.0, per_octave=1) == (1.0, 2.0, 4.0, 8.0)
    assert linear_edges(0.0, 1.0, 4) == (0.0, 0.25, 0.5, 0.75, 1.0)
    with pytest.raises(ValueError):
        geometric_edges(0.0, 1.0)
    with pytest.raises(ValueError):
        linear_edges(1.0, 1.0)


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
def test_histogram_properties_hypothesis():
    @settings(max_examples=50, deadline=None)
    @given(st.lists(st.floats(min_value=1e-6, max_value=1e3,
                              allow_nan=False), min_size=1, max_size=64),
           st.floats(min_value=0.0, max_value=1.0))
    def check(vals, q):
        h = Histogram(geometric_edges(1e-6, 1e3))
        for v in vals:
            h.observe(v)
        assert sum(h.counts) == h.count == len(vals)
        est = h.quantile(q)
        assert min(vals) <= est <= max(vals)
    check()


def test_registry_get_or_create():
    m = MetricsRegistry()
    assert m.counter("a") is m.counter("a")
    assert m.gauge("b") is m.gauge("b")
    assert m.histogram("c") is m.histogram("c")
    assert len(m) == 3
    snap = m.snapshot()
    assert set(snap) == {"counters", "gauges", "histograms"}


# ---------------------------------------------------------------------------
# Disabled-mode contract
# ---------------------------------------------------------------------------

def test_disabled_span_is_shared_noop():
    assert not obs.enabled() and obs.state() is None
    s1 = obs.span("x")
    s2 = obs.span("y", {"k": 1})
    assert s1 is s2                                  # one shared object
    with s1:
        obs.instant("nothing", {"k": 2})             # silently dropped
    with pytest.raises(RuntimeError, match="disabled"):
        obs.snapshot()
    with pytest.raises(RuntimeError, match="disabled"):
        obs.export_telemetry()


def test_enable_disable_cycle():
    st_ = obs.enable(capacity=32)
    assert obs.enabled() and obs.state() is st_
    with obs.span("s"):
        pass
    assert len(st_.tracer) == 1
    obs.disable()
    assert obs.state() is None


# ---------------------------------------------------------------------------
# Export / validation round-trip
# ---------------------------------------------------------------------------

def test_telemetry_roundtrip_and_cli_validation(tmp_path):
    st_ = obs.enable(capacity=16)
    with obs.span("phase", {"n": 1}):
        obs.instant("ev")
    st_.metrics.counter("c").inc(3)
    st_.metrics.gauge("g").set(7.0)
    st_.metrics.histogram("h", [1.0, 2.0]).observe(1.5)

    doc = obs.snapshot()
    assert validate_telemetry(doc) == []
    p = obs.export_telemetry(tmp_path / "telemetry.json")
    assert validate_telemetry_file(p) == []
    loaded = json.loads(p.read_text())
    assert loaded["trace"]["recorded"] == 2
    assert loaded["metrics"]["counters"]["c"]["value"] == 3

    cpath = obs.export_chrome_trace(tmp_path / "trace.json")
    chrome = json.loads(cpath.read_text())
    assert {e["name"] for e in chrome["traceEvents"]} == {"phase", "ev"}


def test_validation_rejects_corruption(tmp_path):
    st_ = obs.enable(capacity=4)
    st_.metrics.histogram("h", [1.0]).observe(0.5)
    doc = snapshot(st_.tracer, st_.metrics)

    bad = dict(doc, schema_version=99)
    assert any("schema_version" in e for e in validate_telemetry(bad))

    bad = json.loads(json.dumps(doc))
    bad["metrics"]["histograms"]["h"]["counts"] = [1]      # wrong length
    assert any("len(edges) + 1" in e for e in validate_telemetry(bad))

    bad = json.loads(json.dumps(doc))
    bad["metrics"]["histograms"]["h"]["counts"] = [5, 0]   # sum != count
    assert any("sum" in e for e in validate_telemetry(bad))

    p = tmp_path / "junk.json"
    p.write_text("{nope")
    assert any("corrupt" in e for e in validate_telemetry_file(p))
    assert any("not found" in e
               for e in validate_telemetry_file(tmp_path / "missing.json"))


# ---------------------------------------------------------------------------
# Live-engine non-interference + lifecycle replay
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _family(arch):
    import jax

    from repro.configs import get_config
    from repro.models import family_module, reduced
    cfg = reduced(get_config(arch))
    mod = family_module(cfg)
    return cfg, mod.init(cfg, jax.random.PRNGKey(0), tp=1)


def _mixed(cfg, n=10):
    from repro.launch.serve import make_requests
    return make_requests(cfg, n, 4, seed=0, long_every=3,
                         priorities=(0, 1, 2))


def test_traced_serve_outputs_bit_identical_and_lifecycle_replay():
    from repro.launch.serve import serve_requests
    cfg, params = _family("qwen3-8b")
    kw = dict(slots=3, paged=True, page_size=4, n_pages=8, prefill_chunk=4)

    done0, stats0 = serve_requests(cfg, params, _mixed(cfg), **kw)
    assert obs.state() is None                 # untraced stayed untraced

    st_ = obs.enable()
    done1, stats1 = serve_requests(cfg, params, _mixed(cfg), **kw)
    assert [r.out for r in done1] == [r.out for r in done0]
    assert stats1["preemptions"] == stats0["preemptions"]

    # replay each request's lifecycle from the trace
    life: dict[int, list[str]] = {}
    for ev in st_.tracer.events():
        if ev[NAME].startswith("req."):
            life.setdefault(ev[ARGS]["rid"], []).append(
                ev[NAME].removeprefix("req."))
    by_rid = {r.rid: r for r in done1}
    assert set(life) == set(by_rid)
    for rid, seq in life.items():
        req = by_rid[rid]
        assert seq[0] == "submit" and seq[-1] == "retire"
        assert seq.count("preempt") == req.preemptions
        # every preemption is eventually resumed (all requests finished)
        assert seq.count("resume") == seq.count("preempt")
        assert "first_token" in seq
        # admitted exactly once as fresh; later placements are resumes
        assert seq.count("admit") == 1
        assert seq.index("admit") < seq.index("first_token") \
            < seq.index("retire")
    # the scenario must actually exercise preemption to gate anything
    assert stats1["preemptions"] > 0

    # engine-level spans + gauges landed too
    names = {e[NAME] for e in st_.tracer.events()}
    assert {"serve.step", "serve.decode_step", "serve.prefill_chunk"} \
        <= names
    assert st_.metrics.gauge("serve.pages_free").n_sets > 0
    assert st_.metrics.counter("serve.preemptions").value \
        == stats1["preemptions"]


def test_disabled_serve_creates_no_events_or_metrics():
    from repro.launch.serve import serve_requests
    cfg, params = _family("qwen3-8b")
    st_ = obs.enable()
    obs.disable()                    # session object kept, singleton cleared
    serve_requests(cfg, params, _mixed(cfg, n=4), slots=2, paged=True,
                   page_size=4, n_pages=8, prefill_chunk=4)
    assert len(st_.tracer) == 0 and st_.tracer.recorded == 0
    assert len(st_.metrics) == 0


# ---------------------------------------------------------------------------
# Profiler sink: spans as TraceAnnotations on the device trace's clock
# ---------------------------------------------------------------------------

ENGINE_SPANS = {"serve.step", "serve.admit", "serve.prefill_init",
                "serve.swap_in", "serve.prefill_tick", "serve.prefill_chunk",
                "serve.commit", "serve.decode_tick", "serve.grow",
                "serve.decode_inputs", "serve.decode_step",
                "serve.decode_sync", "serve.retire"}


def _profiled(tmp_path, fn):
    """Run ``fn`` under a JAX profiler session (Python tracer off, as the
    benchmark traces); returns the host events as (name, start_ns, end_ns,
    stats)."""
    import glob

    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (found,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                         recursive=True)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for p in ProfileData.from_file(found).planes
            if p.name.startswith("/host:") for ln in p.lines
            for e in ln.events]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_profiled_engine_spans_nest_and_carry_request_ids(tmp_path):
    from repro.launch.serve import serve_requests
    cfg, params = _family("qwen3-8b")
    kw = dict(slots=3, paged=True, page_size=4, n_pages=8, prefill_chunk=4)
    serve_requests(cfg, params, _mixed(cfg), **kw)           # compile first
    out = {}
    events = _profiled(tmp_path, lambda: out.update(
        stats=serve_requests(cfg, params, _mixed(cfg), **kw)[1]))
    assert obs.state() is None                 # the ring stayed off
    assert out["stats"]["preemptions"] > 0     # swap_in is exercised
    spans = [e for e in events if e[0].startswith("serve.")]
    assert {e[0] for e in spans} == ENGINE_SPANS

    def of(name):
        return [e for e in spans if e[0] == name]

    steps, ticks, decodes = of("serve.step"), of("serve.decode_tick"), \
        of("serve.decode_step")
    for sync in of("serve.decode_sync"):
        dec = next(d for d in decodes if _inside(sync, d))
        tick = next(t for t in ticks if _inside(dec, t))
        assert any(_inside(tick, s) for s in steps)
    for child in ("serve.prefill_init", "serve.swap_in"):
        assert all(any(_inside(e, a) for a in of("serve.admit"))
                   for e in of(child))
    for child in ("serve.prefill_chunk", "serve.commit"):
        assert all(any(_inside(e, a) for a in of("serve.prefill_tick"))
                   for e in of(child))
    for child in ("serve.grow", "serve.decode_inputs", "serve.retire"):
        assert all(any(_inside(e, a) for a in ticks) for e in of(child))
    # spans of one request share its id; a decode step names its batch
    rids = {r.rid for r in _mixed(cfg)}
    for name in ("serve.prefill_init", "serve.swap_in",
                 "serve.prefill_chunk", "serve.commit"):
        assert all(e[3]["rid"] in rids for e in of(name)), name
    assert all(e[3]["tokens"] > 0 for e in of("serve.prefill_chunk"))
    assert all(e[3]["rows"] > 0 for e in of("serve.commit"))
    assert all(1 <= e[3]["batch"] <= 3 for e in decodes)
    # the session is over: the disabled path is the shared no-op again
    assert obs.span("serve.step") is obs.NULL_SPAN


def test_profiler_and_ring_record_together(tmp_path):
    st_ = obs.enable(capacity=16)

    def work():
        with obs.span("outer", {"k": 1}):
            obs.instant("mark", {"j": 2})

    events = _profiled(tmp_path, work)
    outer = next(e for e in events if e[0] == "outer")
    mark = next(e for e in events if e[0] == "mark")
    assert outer[3] == {"k": 1} and mark[3] == {"j": 2}
    assert _inside(mark, outer) and mark[2] - mark[1] < outer[2] - outer[1]
    assert [e[NAME] for e in st_.tracer.events()] == ["mark", "outer"]
    assert not obs.profiling()
    obs.disable()
    assert obs.span("outer", {"k": 1}) is obs.NULL_SPAN
    assert not obs.active()


@pytest.mark.parametrize("arch,scopes", [
    ("granite-moe-3b-a800m", {"moe"}),
    ("qwen3-8b", {"mlp"}),
])
def test_paged_programs_carry_named_scopes_and_names(arch, scopes):
    """The batched decode and the chunked prefill are two programs, both
    named ``decode_step...``; their ops name the model's layers."""
    import re

    import jax.numpy as jnp

    from repro.launch.serve import _paged_jitted_steps, _paged_prefill_step
    from repro.models import family_module
    cfg, params = _family(arch)
    mod = family_module(cfg)
    slots, max_seq = 2, 16
    cache = mod.init_paged_cache(cfg, slots, slots * max_seq, max_seq, 1)
    decode, _, _ = _paged_jitted_steps(cfg, 1, "xla")
    i32 = functools.partial(jnp.zeros, dtype=jnp.int32)
    hlo = decode.lower(params, cache, i32((slots, 1)), i32((slots,)),
                       i32((slots, max_seq))).compile().as_text()
    prefill = _paged_prefill_step(cfg, 1, "xla").lower(
        params, mod.init_prefill_cache(cfg, 1, 8, 1), i32((1, 4)),
        i32((1,))).compile().as_text()
    assert hlo.startswith("HloModule jit_decode_step_batch")
    assert prefill.startswith("HloModule jit_decode_step_prefill")
    found = {part for name in re.findall(r'op_name="([^"]*)"', hlo)
             for part in name.split("/")}
    assert {"layers", "attention", "kv_write", "lm_head", "embed"} | scopes \
        <= found
    assert not ({"moe", "mlp"} - scopes) & found


def test_serve_stats_latency_summaries_match_percentiles():
    from repro.launch.serve import serve_requests
    cfg, params = _family("qwen3-8b")
    done, stats = serve_requests(cfg, params, _mixed(cfg, n=8), slots=2,
                                 paged=True, page_size=4, n_pages=8,
                                 prefill_chunk=4)
    for key, vals in (
            ("ttft_s", [r.queue_latency for r in done]),
            ("queue_wait_s", [r.admit_time - r.submit_time for r in done])):
        s = stats[key]
        assert s["count"] == len(done)
        # Histogram.quantile is an inverted-CDF estimator (first value whose
        # cumulative count reaches q*n, interpolated inside its bucket) — so
        # compare against the same definition; numpy's default linear method
        # interpolates BETWEEN order statistics, which a histogram cannot see
        ref = np.percentile(vals, [50, 95, 99], method="inverted_cdf")
        # 512 linear buckets over the observed range: interpolation error is
        # bounded by one bucket width
        tol = (max(vals) - min(vals)) / 512 + 1e-12
        assert abs(s["p50"] - ref[0]) <= tol
        assert abs(s["p95"] - ref[1]) <= tol
        assert abs(s["p99"] - ref[2]) <= tol
        assert math.isclose(s["mean"], float(np.mean(vals)))


# ---------------------------------------------------------------------------
# Satellites: measurement failure capture, DB persistence, cache forwarding
# ---------------------------------------------------------------------------

def _gemm_point(n=8):
    """A small gemm workload with a matching hw config + schedule."""
    from repro.core.hw_primitives import HWConfig
    from repro.core.intrinsics import GEMM
    from repro.core.matching import match
    from repro.core.sw_primitives import Schedule
    from repro.core.workloads import gemm

    w = gemm(n, n, n)
    choice = match(GEMM, w)[0]
    tiles = tuple(sorted((c, n) for c in choice.mapped_compute_indices))
    hw = HWConfig(intrinsic="GEMM", pe_rows=8, pe_cols=8, pe_depth=8,
                  vmem_kib=2048)
    return w, hw, Schedule(choice, tiles, tuple(w.all_indices()), 0)


def test_measure_failure_captures_elapsed_and_error_type():
    from repro.tuner.measure import MeasureOptions, measure_one

    w, hw, sched = _gemm_point()
    # impossible block-volume cap forces a ValueError in lower()
    res = measure_one(w, hw, sched, MeasureOptions(max_block_elems=1))
    assert not res.ok
    assert res.error_type == "ValueError"
    assert res.error.startswith("ValueError:")
    assert res.elapsed_s >= 0.0
    ok = measure_one(w, hw, sched, MeasureOptions())
    assert ok.ok and ok.elapsed_s > 0.0 and ok.error_type == ""


def test_tuning_db_failures_section_roundtrip(tmp_path):
    from repro.tuner.db import TuningDB
    p = tmp_path / "db.json"
    db = TuningDB(p)
    db.add_failures([{"workload": "w0", "error_type": "ValueError",
                      "error": "ValueError: boom", "elapsed_s": 0.1,
                      "backend": "interpret", "app": "t"}])
    db.save(p)

    back = TuningDB.load(p)
    assert len(back.failures) == 1
    assert back.failures[0]["error_type"] == "ValueError"
    # load + save again must not duplicate (content dedup)
    back.save(p)
    assert len(TuningDB.load(p).failures) == 1
    # old-reader tolerance: a malformed section loads as empty, warning only
    p2 = tmp_path / "bad.json"
    p2.write_text(json.dumps({"version": 1, "records": {},
                              "calibration": {}, "apps": {},
                              "failures": "nope"}))
    with pytest.warns(UserWarning, match="failures"):
        assert TuningDB.load(p2).failures == []


def test_measured_codesign_persists_failures(tmp_path):
    from repro.core.codesign import codesign
    from repro.core.workloads import gemm
    from repro.tuner.db import TuningDB
    from repro.tuner.measure import MeasureOptions

    p = tmp_path / "db.json"
    rep = codesign([gemm(8, 8, 8)], intrinsics=["GEMM"], n_trials=2,
                   n_init=2, seed=0, measure=True, measure_top_k=1,
                   measure_opts=MeasureOptions(max_block_elems=1),
                   db_path=p, app="failtest")
    assert rep.db_path == p
    fails = TuningDB.load(p).failures
    assert fails and all(f["app"] == "failtest" for f in fails)
    assert all(f["error_type"] == "ValueError" for f in fails)
    assert all(f["elapsed_s"] >= 0.0 for f in fails)


def test_evalcache_hit_rate_and_calibrated_model_forwarding():
    from repro.core.cost_model import EvalCache, evaluate
    from repro.tuner.calibrate import Calibration, CalibratedCostModel

    w, hw, sched = _gemm_point()
    cache = EvalCache()
    assert cache.hit_rate == 0.0

    model = CalibratedCostModel(Calibration(), target="spatial", cache=cache)
    r1 = model.evaluate(w, sched, hw)          # miss: attached cache used
    r2 = model.evaluate(w, sched, hw)          # hit
    assert r1.latency_s == r2.latency_s
    assert (model.cache_hits, model.cache_misses) == (1, 1)
    assert model.cache_hit_rate == 0.5
    assert cache.stats()["hit_rate"] == 0.5
    # an explicit per-call cache still overrides the attached one
    other = EvalCache()
    model.evaluate(w, sched, hw, cache=other)
    assert other.misses == 1 and model.cache_misses == 1

    # parity with the raw evaluate through the same cache protocol
    raw = evaluate(w, sched, hw, "spatial")
    assert math.isclose(r1.latency_s, raw.latency_s)


def test_codesign_emits_spans_and_cache_gauges():
    from repro.core.codesign import codesign
    from repro.core.workloads import gemm

    st_ = obs.enable()
    # n_trials must exceed n_init: the init design satisfies the first
    # n_init trials, and only the while-loop beyond them emits mobo.trial
    codesign([gemm(8, 8, 8)], intrinsics=["GEMM"], n_trials=4, n_init=2,
             seed=0)
    names = {e[NAME] for e in st_.tracer.events()}
    assert {"codesign.run", "codesign.intrinsic", "codesign.hw_dse",
            "codesign.refine", "mobo.trial", "mobo.fit_gps",
            "sw_dse.run_searches", "sw_dse.round"} <= names
    assert st_.metrics.gauge("evalcache.entries").value > 0
    assert st_.metrics.counter("mobo.trials").value > 0
    hv_evs = [e for e in st_.tracer.events() if e[NAME] == "mobo.hv"]
    assert hv_evs and all("hv" in e[ARGS] for e in hv_evs)
