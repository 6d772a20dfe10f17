"""The transformer family's FLOP and byte counts against numbers worked by
hand, the configuration files against the program, the weights' layout,
the peaks table, and the weights, reference logits and counts against
goldens recorded from the tree before the family's code moved into
``families/transformer.py``."""
import hashlib
import json

import jax
import numpy as np
import pytest

from bench import spec, weights
from bench.spec import BENCH

import bench.run as run_mod

transformer = spec.family({"family": "transformer"})
DATA = BENCH / "tests" / "data"
PARITY = json.loads((DATA / "parity.json").read_text())


def _cfg(name):
    with open(BENCH / "configs" / f"{name}.json") as f:
        return spec.model_config(json.load(f))


def test_granite_moe_padded_against_routed_work_per_token():
    cfg = _cfg("granite-moe-3b-a800m")
    # one decode token fills every expert's capacity of 8 rows:
    # 40 * 8 * 3 * 1536 * 512 * 2 = 1.51 GFLOP per layer, 37.7 MFLOP routed
    assert transformer.moe_padded_flops(cfg, 1) == 40 * 8 * 3 * 1536 * 512 * 2
    assert transformer.moe_padded_flops(cfg, 1) == pytest.approx(1.51e9,
                                                              rel=2e-3)
    assert transformer.moe_routed_flops(cfg, 1) == pytest.approx(37.7e6,
                                                              rel=2e-3)
    # at 32 live slots: about 1.55 TFLOP a step over 32 layers
    assert 32 * 32 * transformer.moe_padded_flops(cfg, 1) == \
        pytest.approx(1.55e12, rel=2e-2)


def test_kv_bytes_per_token():
    assert transformer.kv_bytes_per_token(_cfg("granite-moe-3b-a800m")) \
        == 64 * 1024
    assert transformer.kv_bytes_per_token(_cfg("qwen3-8b-l12")) == 48 * 1024


def test_weight_bytes_match_the_weights_made():
    for name in ("granite-moe-3b-a800m", "qwen3-8b-l12"):
        cfg = _cfg(name)
        made = sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(
                       weights.shapes(transformer, cfg)))
        embed = cfg.vocab * cfg.d_model * 2     # a step reads only B rows
        assert transformer.weight_bytes(cfg) == made - embed


def test_decode_step_counts():
    cfg = _cfg("qwen3-8b-l12")
    flops, nbytes = transformer.decode_step(cfg, [100, 200])
    per_token = [transformer.token_flops(cfg, c, True) for c in (100, 200)]
    assert flops == sum(per_token)
    assert nbytes == transformer.weight_bytes(cfg) + 2 * 4096 * 2 \
        + 48 * 1024 * 300
    # dense: 2 * (attention projections + MLP) per layer, + the head
    d = 4096
    proj = d * (32 + 16) * 128 + 32 * 128 * d
    assert transformer.token_flops(cfg, 1, True) == \
        12 * (2 * proj + 4 * 32 * 128 + 6 * d * 12288) + 2 * d * 151936
    assert transformer.prefill_chunk(cfg, 0, 2, True) == \
        transformer.token_flops(cfg, 1, False) + \
        transformer.token_flops(cfg, 2, True)


def test_expected_experts_touched():
    cfg = _cfg("granite-moe-3b-a800m")
    assert transformer.experts_touched(cfg, 1) == pytest.approx(8)
    assert transformer.experts_touched(cfg, 32) == pytest.approx(40, abs=0.05)


def test_weights_have_the_programs_layout():
    from repro.models import family_module
    cfg = _cfg("qwen3-8b-l12")
    ours = weights.shapes(transformer, cfg)
    theirs = jax.eval_shape(lambda k: family_module(cfg).init(cfg, k),
                            jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(ours) == \
        jax.tree_util.tree_structure(theirs)
    for a, b in zip(jax.tree_util.tree_leaves(ours),
                    jax.tree_util.tree_leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_configuration_file_must_agree_with_the_program():
    with open(BENCH / "configs" / "qwen3-8b-l12.json") as f:
        c = json.load(f)
    c["config"]["hidden_size"] = 4000
    with pytest.raises(ValueError):
        spec.model_config(c)


def test_unknown_device_kind_is_an_error():
    assert run_mod._peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        run_mod._peaks("TPU v9 imaginary")


def _digest(a) -> str:
    a = np.asarray(a)
    return hashlib.sha256(a.tobytes()).hexdigest() + f":{a.dtype}:{a.shape}"


TOYS = ("toy.json", "toy-qwen3.json")


@pytest.mark.parametrize("seed", [5, 2**31 + 11])
@pytest.mark.parametrize("fname", TOYS)
def test_weights_match_the_parent_tree(fname, seed):
    config = json.loads((DATA / fname).read_text())
    w = weights.make(spec.family(config), spec.model_config(config), seed)
    assert {jax.tree_util.keystr(p): _digest(x) for p, x in
            jax.tree_util.tree_leaves_with_path(w)} == \
        PARITY["weights"][f"{fname}@{seed}"]


@pytest.mark.parametrize("mode", [None, "fp8"])
@pytest.mark.parametrize("fname", TOYS)
def test_reference_logits_match_the_parent_tree(fname, mode):
    config = json.loads((DATA / fname).read_text())
    fam = spec.family(config)
    w = weights.make(fam, spec.model_config(config), 5)
    seqs = [np.arange(1, 9, dtype=np.int32), np.arange(3, 14, dtype=np.int32)]
    at = [np.arange(8), np.arange(2, 11)]
    got = fam.logits(w, fam.Dims.of(config), seqs, at, pad_to=16, mode=mode)
    assert [_digest(g) for g in got] == PARITY["logits"][f"{fname}@{mode}"]


@pytest.mark.parametrize("name", ["granite-moe-3b-a800m", "qwen3-8b-l12"])
def test_counts_match_the_parent_tree(name):
    cfg = _cfg(name)
    got = {}
    for ctx in ([1], [100, 200], list(range(1, 1281, 40)), [12288] * 5):
        got[f"decode_step{ctx[:3]}x{len(ctx)}"] = list(
            transformer.decode_step(cfg, ctx))
    for args in ((0, 128, False), (896, 128, True), (0, 16, True),
                 (4096, 128, False)):
        got[f"prefill_chunk{list(args)}"] = \
            transformer.prefill_chunk(cfg, *args)
    assert got == PARITY["counts"][name]
