"""``correct`` on a toy cell, driven end to end on the CPU: the sound run
passes, the control (the reference computed in float8 put in the program's
place) fails, and so does a run whose timed path is broken underneath.

The toy cell is a two-layer MoE at toy widths in a root of its own, every
finished request compared (40 a run, about 350 tokens).  As in the granite
cell, the widest gap does not separate on a MoE: a near-tie at a router's
top-k boundary, rounded the other way in bf16, moves one token's logits by
a tenth (sound runs read up to 0.133 on seeds 1 to 12, the control 0.068 at
the least), so the toy compares the mean gap.  Its limit (0.004) lies
between what sound runs read on seeds 1 to 12 (at most 0.00141) and what
the control reads on them (0.0080 and more), measured on the CPU.
"""
import json
import shutil

import jax.numpy as jnp
import pytest

from bench import check, reference, spec, weights
from bench.spec import BENCH, REPO

import bench.run as run_mod

CELL = "toy.chat"
MEAN_LIMIT = 0.004
SAMPLE = 40


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_root")
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    for sub in ("configs", "cells", "traffic"):
        (root / "bench" / sub).mkdir(parents=True)
    shutil.copy(BENCH / "tests" / "data" / "toy.json",
                root / "bench" / "configs" / "toy.json")
    b["configs"] = [{"name": "toy", "source": "toy", "reduced": [],
                     "file": "bench/configs/toy.json", "why": "toy"}]
    b["workloads"] = [{"name": CELL, "config": "toy", "traffic": "toy-chat",
                       "chips": 1, "why": "toy"}]
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL]
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    (root / "bench" / "traffic" / "toy-chat.json").write_text(json.dumps({
        "arrivals": "open",
        "prompt": {"dist": "lognormal", "median": 16, "sigma": 1.0,
                   "min": 4, "max": 64, "grid": [4, 16, 32, 64]},
        "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                   "min": 2, "max": 16}}))
    (root / "bench" / "cells" / f"{CELL}.json").write_text(json.dumps({
        "engine": {"slots": 4, "max_seq": 128, "page_size": 8,
                   "prefill_chunk": 16},
        "load": {"rate_per_s": 20.0},
        "correct": {"sample_requests": SAMPLE,
                    "mean_logit_gap": MEAN_LIMIT}}))
    return root


def _run(root, seed, break_path=None):
    kept = {}

    def hook(run):
        kept["run"] = run
        if break_path is not None:
            break_path(run.engine)

    result = run_mod.run_cell(CELL, seed, 2.0, False, require_chip=False,
                              root=root, break_path=hook)
    return result, kept["run"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_sound_run_is_correct_and_the_control_is_not(root, seed):
    result, run = _run(root, seed)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] == 40
    assert set(result["metrics"]) == {"output_tok_s", "ttft_p95_s",
                                      "itl_p95_ms", "setup_s"}
    cell = spec.load_cell(CELL, root)
    cfg = spec.model_config(cell.config)
    served, control = check.control_gap(
        weights.make(cfg, seed), reference.Dims.of(cell.config), run,
        check.sample(run, seed, SAMPLE), pad_to=128, mode="fp8")
    assert served["mean_logit_gap"] <= MEAN_LIMIT < control["mean_logit_gap"]


def _state_unchanged(engine):
    step = engine._decode_batch

    def broken(params, cache, toks, pos, row_map):
        return step(params, cache, toks, pos, row_map)[0], cache
    engine._decode_batch = broken


def _half_batch_left_out(engine):
    step = engine._decode_batch

    def broken(params, cache, toks, pos, row_map):
        return step(params, cache, toks.at[1::2].set(0), pos, row_map)
    engine._decode_batch = broken


def _token_altered(engine):
    step = engine._decode_batch

    def broken(params, cache, toks, pos, row_map):
        logits, cache = step(params, cache, toks, pos, row_map)
        return jnp.roll(logits, 1, axis=-1), cache
    engine._decode_batch = broken


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch_left_out,
                                   _token_altered])
def test_broken_timed_path_is_not_correct(root, fault):
    result, _ = _run(root, 1, fault)
    assert not result["correct"]
    assert result["checks"]["mean_logit_gap"]["value"] > MEAN_LIMIT


def test_sample_takes_one_finished_request_from_each_slot():
    from types import SimpleNamespace as NS

    from bench.stats import Served

    served, requests = {}, {}
    for i in range(20):     # slot i % 5; request 7 the longest; 3 failed
        served[i] = Served(due=None, sent=0.0, ok=i != 3, slot=i % 5)
        requests[i] = NS(out=[0] * (50 if i == 7 else 10 + i % 4))
    run = NS(served=served, requests=requests)
    for seed in (1, 2, 2**31 + 11):
        idxs = check.sample(run, seed, 5)
        assert idxs[0] == 7 and len(set(idxs)) == 5 and 3 not in idxs
        assert sorted(served[i].slot for i in idxs) == [0, 1, 2, 3, 4]
        assert check.sample(run, seed, 5) == idxs
    assert len(set(check.sample(run, 1, 8))) == 8


def test_reference_applies_the_configuration_scalars():
    import dataclasses

    cfg = spec.model_config(json.loads(
        (BENCH / "tests" / "data" / "toy.json").read_text()))
    c = json.loads((BENCH / "tests" / "data" / "toy.json").read_text())
    dims = reference.Dims.of(c)
    assert dims.attn_scale == 0.0 and dims.embed_mult == 1.0
    w = weights.make(cfg, 5)
    seqs, at = [jnp.arange(1, 9)], [jnp.arange(8)]
    base = reference.logits(w, dims, seqs, at, pad_to=16)[0]
    scaled = reference.logits(w, dataclasses.replace(dims, logits_div=2.0),
                              seqs, at, pad_to=16)[0]
    assert scaled == pytest.approx(base / 2, rel=1e-5)
    # head_dim ** -0.5 stated as attention_multiplier reads the same
    c["config"]["attention_multiplier"] = 16 ** -0.5
    same = reference.logits(w, reference.Dims.of(c), seqs, at, pad_to=16)[0]
    assert (same == base).all()
    for key, value in (("embedding_multiplier", 12.0),
                       ("residual_multiplier", 0.22),
                       ("tie_word_embeddings", True)):
        c2 = json.loads(json.dumps(c))
        c2["config"][key] = value
        moved = reference.logits(w, reference.Dims.of(c2), seqs, at,
                                 pad_to=16)[0]
        assert abs(moved - base).max() > 1e-3, key
