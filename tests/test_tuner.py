"""Measured-autotuning subsystem (repro.tuner): lowering + timing,
failure capture, calibration (held-out rank improvement), tuning-DB
integration with kernel dispatch, and the measured codesign loop."""
import math

import numpy as np
import pytest

from repro.core import workloads as W
from repro.core.codesign import Constraints, codesign
from repro.core.cost_model import evaluate_batch, evaluate_batch_reports
from repro.core.hw_primitives import HWBuilder, HWConfig
from repro.core.intrinsics import GEMM
from repro.core.matching import match
from repro.core.sw_primitives import Schedule
from repro.tuner import calibrate as C
from repro.tuner import measure as M
from repro.tuner.db import TuningDB


@pytest.fixture
def gemm64():
    wl = W.gemm(64, 64, 64, name="g64")
    return wl, match(GEMM, wl)[0]


def _hw(rows=16, cols=16, depth=16, **kw):
    kw.setdefault("vmem_kib", 2048)
    return HWConfig(intrinsic="GEMM", pe_rows=rows, pe_cols=cols,
                    pe_depth=depth, **kw)


def _sched(wl, choice, tile, order=None):
    tiles = tuple(sorted((c, tile) for c in choice.mapped_compute_indices))
    return Schedule(choice, tiles, tuple(order or wl.all_indices()), 0)


# ---------------------------------------------------------------------------
# classification + lowering
# ---------------------------------------------------------------------------

def test_classify_families():
    assert M.classify(W.gemm(8, 8, 8))[0] == "gemm"
    assert M.classify(W.gemv(8, 8))[0] == "gemv"
    assert M.classify(W.conv2d(4, 4, 6, 6))[0] == "conv2d"
    assert M.classify(W.ttm(4, 4, 4, 4)) is None     # no kernel family
    assert M.classify(W.mttkrp(4, 4, 4, 4)) is None


def test_measure_one_gemm_interpret(gemm64):
    wl, choice = gemm64
    res = M.measure_one(wl, _hw(), _sched(wl, choice, 32),
                        M.MeasureOptions(warmup=1, repeats=3))
    assert res.ok and res.latency_s > 0
    assert res.point.op == "gemm" and res.point.shape == (64, 64, 64)
    # tiles of 32 on a 16-block hw pad to 32 exactly
    assert res.point.block_map == {"bm": 32, "bn": 32, "bk": 32}
    assert len(res.times_s) == 3


def test_measure_failure_capture_no_lowering():
    wl = W.ttm(8, 8, 8, 8)
    gm = W.gemm(8, 8, 8)
    choice = match(GEMM, gm)[0]
    res = M.measure_one(wl, _hw(), _sched(gm, choice, 8))
    assert not res.ok and math.isinf(res.latency_s)
    assert "no kernel lowering" in res.error


def test_measure_batch_dedups_identical_lowerings(gemm64):
    wl, choice = gemm64
    hw = _hw()
    # two schedules, same padded blocks -> one measurement shared
    pop = [_sched(wl, choice, 32),
           _sched(wl, choice, 32, order=reversed(wl.all_indices())),
           _sched(wl, choice, 64)]
    out = M.measure_batch(wl, hw, pop, M.MeasureOptions(warmup=1, repeats=3))
    assert all(r.ok for r in out)
    assert out[0].times_s == out[1].times_s      # served from the memo
    assert out[2].point != out[0].point


# ---------------------------------------------------------------------------
# static legality gate (repro.analysis.legality ahead of lowering)
# ---------------------------------------------------------------------------

def test_measure_one_skips_statically_illegal(gemm64):
    wl, choice = gemm64
    hw = _hw(vmem_kib=16)                        # 16 KiB scratchpad
    res = M.measure_one(wl, hw, _sched(wl, choice, 64))   # 48 KiB tiles
    assert not res.ok and math.isinf(res.latency_s)
    assert res.error_type == "Illegal"
    assert res.point is None and res.times_s == ()
    assert "legality/vmem-overflow" in res.error
    # same hw point is inside the design space: a fitting tile measures
    ok = M.measure_one(wl, hw, _sched(wl, choice, 16),
                       M.MeasureOptions(warmup=1, repeats=2))
    assert ok.ok and ok.error_type == ""


def test_measure_batch_lowers_only_legal_candidates(gemm64):
    wl, choice = gemm64
    hw = _hw(vmem_kib=16)
    pop = [_sched(wl, choice, 16),               # legal
           _sched(wl, choice, 64),               # statically illegal
           _sched(wl, choice, 16,                # legal dup -> memo-served
                  order=reversed(wl.all_indices()))]
    out = M.measure_batch(wl, hw, pop, M.MeasureOptions(warmup=1, repeats=2))
    assert out[0].ok and out[2].ok
    assert out[2].times_s == out[0].times_s      # dedup still works
    assert out[1].error_type == "Illegal" and out[1].point is None
    s = M.summarize_batch(out)
    assert s["candidates"] == 3 and s["illegal"] == 1
    assert s["measured"] == 2 and s["deduped"] == 1 and s["failed"] == 0


def test_pallas_backend_gates_blocks_the_chip_refuses(gemm64, monkeypatch):
    """(16, 16, 16) blocks of a 64^3 GEMM break the (8, 128) tiling rule:
    the interpreter runs them, the chip's compiler would not, so on backend
    'pallas' they are skipped unrun; whole-array (64, 64, 64) blocks pass."""
    wl, choice = gemm64
    monkeypatch.setattr(M, "lower", lambda *a, **k: (_ for _ in ()).throw(
        AssertionError("illegal candidate was lowered")))
    res = M.measure_one(wl, _hw(), _sched(wl, choice, 16),
                        M.MeasureOptions(backend="pallas"))
    assert res.error_type == "Illegal" and "legality/block-tiling" in res.error
    pt = M.kernel_point(wl, _hw(), _sched(wl, choice, 64),
                        M.MeasureOptions(backend="pallas"))
    assert pt.block_map == {"bm": 64, "bn": 64, "bk": 64}
    assert M._static_illegal(wl, _hw(), _sched(wl, choice, 64),
                             M.MeasureOptions(backend="pallas")) == []


@pytest.mark.parametrize("op,shape,blocks,rule", [
    ("gemm", (512, 1536, 1536), {"bm": 16, "bn": 48, "bk": 64},
     "legality/block-tiling"),
    ("gemm", (2048, 3072, 3072), {"bm": 512, "bn": 1024, "bk": 1024},
     "legality/block-vmem"),
    ("gemm", (2048, 3072, 3072), {"bm": 256, "bn": 1024, "bk": 1024}, None),
    ("gemv", (12288, 4096), {"bm": 512, "bk": 512}, None),
    ("dot", (1000,), {"bk": 64}, "legality/block-tiling"),
    ("conv2d", (64, 64, 56, 56, 3, 3), {"bk": 64}, None),
    ("conv2d", (64, 64, 56, 56, 3, 3), {"bk": 12}, "legality/block-tiling"),
])
def test_verify_kernel_blocks_matches_v5e_compiles(op, shape, blocks, rule):
    """Each case is what a v5e compile of the kernel accepted or refused."""
    from repro.analysis.legality import verify_kernel_blocks
    found = [f.rule for f in verify_kernel_blocks(op, shape, blocks, 4)]
    assert set(found) == ({rule} if rule else set())


def test_codesign_on_chip_refuses_analytical_standins(monkeypatch):
    """Backend 'pallas' with nothing timeable must fail, not commit a
    latency that no kernel measured."""
    from repro.tuner import measure as M_

    monkeypatch.setattr(M_, "measure_one", lambda *a, **k: M_.MeasureResult(
        latency_s=math.inf, error="forced failure"))
    with pytest.raises(RuntimeError, match="no timed kernel"):
        codesign([W.gemm(64, 64, 64, name="g0")], intrinsics=["GEMM"],
                 n_trials=4, n_init=2, seed=0, target="tpu", measure=True,
                 measure_backend="pallas", measure_top_k=2)


def test_illegal_skip_never_retried_or_quarantined(gemm64, monkeypatch):
    wl, choice = gemm64
    calls = []
    monkeypatch.setattr(M, "lower",
                        lambda *a, **k: calls.append(a) or (_ for _ in ()).throw(
                            AssertionError("illegal candidate was lowered")))
    res = M.measure_one(wl, _hw(vmem_kib=16), _sched(wl, choice, 64),
                        quarantine={("gemm", (64, 64, 64))})
    assert res.error_type == "Illegal" and calls == []


def test_measure_batch_mixes_failures_and_successes(gemm64):
    wl, choice = gemm64
    good = _sched(wl, choice, 32)
    opts = M.MeasureOptions(warmup=0, repeats=1, max_block_elems=8)
    out = M.measure_batch(wl, _hw(), [good], opts)   # volume cap trips
    assert len(out) == 1 and not out[0].ok and "max_block_elems" in out[0].error
    # on the chip the cap does not apply (VMEM bounds the blocks there)
    pt, _ = M.lower(wl, _hw(), good, M.MeasureOptions(backend="pallas",
                                                      max_block_elems=8))
    assert pt.block_map == {"bm": 32, "bn": 32, "bk": 32}


# ---------------------------------------------------------------------------
# calibration
# ---------------------------------------------------------------------------

def test_calibrated_model_identity_matches_evaluate_batch(gemm64):
    wl, choice = gemm64
    hw = _hw()
    pop = [_sched(wl, choice, t) for t in (16, 32, 64)]
    raw = evaluate_batch(wl, hw, pop, "tpu")
    model = C.CalibratedCostModel(C.Calibration())
    np.testing.assert_allclose(model.evaluate_batch(wl, hw, pop, "tpu"), raw)


def test_calibrated_model_offset_scales_latency_only(gemm64):
    wl, choice = gemm64
    hw = _hw()
    pop = [_sched(wl, choice, t) for t in (16, 32)]
    raw = evaluate_batch(wl, hw, pop, "tpu")
    cal = C.Calibration({"gemm": C.Correction("offset", offset=math.log(3.0),
                                              n_samples=4)})
    ys = C.CalibratedCostModel(cal).evaluate_batch(wl, hw, pop, "tpu")
    np.testing.assert_allclose(ys[:, 0], raw[:, 0] * 3.0, rtol=1e-12)
    np.testing.assert_allclose(ys[:, 1:], raw[:, 1:])


def test_fit_degrades_gracefully_with_few_samples(gemm64):
    wl, choice = gemm64
    reports = evaluate_batch_reports(wl, _hw(), [_sched(wl, choice, 32)],
                                     "tpu")
    cal = C.fit([("gemm", reports[0], 1e-3)] * 2)
    assert cal.for_op("gemm").kind == "offset"
    assert cal.for_op("gemv").kind == "identity"
    assert C.fit([]).for_op("gemm").kind == "identity"


def test_spearman_basics():
    assert C.spearman([1, 2, 3], [10, 20, 30]) == pytest.approx(1.0)
    assert C.spearman([1, 2, 3], [3, 2, 1]) == pytest.approx(-1.0)
    assert math.isnan(C.spearman([1.0], [2.0]))


def test_calibration_improves_heldout_spearman(gemm64):
    """The acceptance gate: on a GEMM candidate population, fitting the
    per-op correction on a train split improves the Spearman rank
    correlation between predicted and *measured* (interpret-mode) latency
    on the held-out split.  The population varies hardware knobs the
    interpreter cannot see (banks, dataflow, burst) so the raw analytical
    ordering is meaningfully scrambled."""
    wl, choice = gemm64
    rng = np.random.default_rng(7)
    loops = list(choice.mapped_compute_indices)
    hws, pop = [], []
    for _ in range(48):
        hws.append(HWConfig(
            intrinsic="GEMM", pe_rows=int(rng.choice([8, 16, 32])),
            pe_cols=int(rng.choice([8, 16, 32])),
            pe_depth=int(rng.choice([8, 16, 32])),
            vmem_kib=int(rng.choice([256, 1024, 4096])),
            banks=int(rng.choice([1, 2])),
            burst_bytes=int(rng.choice([256, 1024, 4096])),
            dataflow=str(rng.choice(["OS", "WS", "IS"]))))
        tiles = tuple(sorted((c, int(rng.choice([16, 32, 64])))
                             for c in loops))
        order = list(wl.all_indices())
        rng.shuffle(order)
        pop.append(Schedule(choice, tiles, tuple(order), 0))

    reports = evaluate_batch_reports(wl, hws, pop, "tpu")
    meas = M.measure_batch(wl, hws, pop,
                           M.MeasureOptions(warmup=2, repeats=9))
    assert all(r.ok for r in meas)
    pred = np.array([r.latency_s for r in reports])
    truth = np.array([m.latency_s for m in meas])

    # two-fold cross-fit (fit on one half, score on the other, average):
    # halves the variance wall-clock rank noise injects on shared runners
    half = len(pop) // 2
    folds = [(slice(0, half), slice(half, None)),
             (slice(half, None), slice(0, half))]
    befores, afters = [], []
    for fit_sl, eval_sl in folds:
        cal = C.fit(C.collect_samples(wl, reports[fit_sl], meas[fit_sl]))
        assert cal.for_op("gemm").kind == "linear"
        corrected = C.CalibratedCostModel(cal).predict_latency(
            wl, reports[eval_sl])
        befores.append(C.spearman(pred[eval_sl], truth[eval_sl]))
        afters.append(C.spearman(corrected, truth[eval_sl]))

    before, after = float(np.mean(befores)), float(np.mean(afters))
    assert after > before, (befores, afters)
    assert after >= 0.4, (befores, afters)


# ---------------------------------------------------------------------------
# dispatch integration + measured codesign end-to-end
# ---------------------------------------------------------------------------

def test_ops_dispatch_defaults_without_db(tmp_path):
    from repro.kernels import ops

    ops.reset_dispatch()
    ops.set_tuning_db(tmp_path / "missing.json")
    try:
        blk = ops.resolve_blocks("gemm", (64, 64, 64), np.float32,
                                 "interpret", bm=None, bn=None, bk=None)
        assert blk == ops.DEFAULT_BLOCKS["gemm"]
        # explicit arguments always win
        blk = ops.resolve_blocks("gemm", (64, 64, 64), np.float32,
                                 "interpret", bm=8, bn=None, bk=None)
        assert blk["bm"] == 8
    finally:
        ops.reset_dispatch()


def test_codesign_measure_end_to_end(tmp_path):
    """codesign --measure produces a tuning DB; dispatch picks the tuned
    block shapes from it; the calibrated model is produced."""
    import jax.numpy as jnp

    from repro.kernels import ops

    db_path = tmp_path / "tuning_db.json"
    wl = [W.gemm(64, 64, 64, name="g0")]
    rep = codesign(wl, intrinsics=["GEMM"], n_trials=4, n_init=2, seed=0,
                   target="tpu", measure=True, measure_top_k=2,
                   measure_opts=M.MeasureOptions(warmup=1, repeats=3),
                   db_path=db_path, app="e2e")
    assert rep.solution is not None
    assert math.isfinite(rep.solution.latency_s)
    assert rep.measured and rep.measured["GEMM"]["measured"] > 0
    # the mixed-total flag always rides the summary; a winner measured on
    # every workload must report False (no analytical stand-ins inside)
    s = rep.measured["GEMM"]
    assert "best_has_fallbacks" in s
    assert isinstance(s["best_has_fallbacks"], bool)
    if s["fallbacks"] == 0:
        assert s["best_has_fallbacks"] is False
    assert rep.calibration is not None and rep.calibration.corrections

    # the DB landed, with a gemm record for the workload's shape + the app
    db = TuningDB.load(db_path)
    blocks = db.best_config("gemm", (64, 64, 64), "float32", "interpret")
    assert blocks and set(blocks) == {"bm", "bn", "bk"}
    assert "e2e" in db.apps and db.calibration.corrections

    # dispatch resolves exactly those measured-best blocks
    ops.reset_dispatch()
    ops.set_tuning_db(db_path)
    try:
        resolved = ops.resolve_blocks("gemm", (64, 64, 64), jnp.float32,
                                      "interpret", bm=None, bn=None, bk=None)
        assert resolved == blocks
        # and the kernel actually runs with them
        rng = np.random.default_rng(0)
        a = jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)
        got = ops.matmul(a, b, implementation="interpret")
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(a) @ np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
        # app-level startup pickup (serve/train path)
        installed = ops.configure(app="e2e", db_path=db_path)
        assert installed and set(installed) == set(ops.DEFAULT_BLOCKS)
    finally:
        ops.reset_dispatch()


def test_measure_rerank_flags_mixed_totals(monkeypatch):
    """Regression: when the winning candidate's total contains analytical
    stand-ins (measurement failed / no lowering), the summary must say so —
    best_measured_total_s is then NOT wall-clock truth."""
    from repro.tuner import measure as M_

    def always_fail(w, hw, sched, opts, quarantine=None):
        return M_.MeasureResult(latency_s=math.inf, error="forced failure")

    monkeypatch.setattr(M_, "measure_one", always_fail)
    wl = [W.gemm(64, 64, 64, name="g0")]
    rep = codesign(wl, intrinsics=["GEMM"], n_trials=4, n_init=2, seed=0,
                   target="tpu", measure=True, measure_top_k=2,
                   measure_opts=M.MeasureOptions(warmup=1, repeats=1))
    s = rep.measured["GEMM"]
    assert s["measured"] == 0 and s["fallbacks"] > 0
    assert s["best_has_fallbacks"] is True


def test_codesign_without_measure_unchanged(tmp_path):
    """measure=False keeps the analytical path and writes nothing."""
    wl = [W.gemm(64, 64, 64, name="g0")]
    rep = codesign(wl, intrinsics=["GEMM"], n_trials=3, n_init=2, seed=0)
    assert rep.measured is None and rep.calibration is None
    assert rep.db_path is None
    assert not list(tmp_path.iterdir())
