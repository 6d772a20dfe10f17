"""FLOP and byte counters against numbers worked by hand, the configuration
files against the program, the weights' layout, and the peaks table."""
import json

import jax
import pytest

from bench import counters, spec, weights
from bench.spec import BENCH

import bench.run as run_mod


def _cfg(name):
    with open(BENCH / "configs" / f"{name}.json") as f:
        return spec.model_config(json.load(f))


def test_granite_moe_padded_against_routed_work_per_token():
    cfg = _cfg("granite-moe-3b-a800m")
    # one decode token fills every expert's capacity of 8 rows:
    # 40 * 8 * 3 * 1536 * 512 * 2 = 1.51 GFLOP per layer, 37.7 MFLOP routed
    assert counters.moe_padded_flops(cfg, 1) == 40 * 8 * 3 * 1536 * 512 * 2
    assert counters.moe_padded_flops(cfg, 1) == pytest.approx(1.51e9,
                                                              rel=2e-3)
    assert counters.moe_routed_flops(cfg, 1) == pytest.approx(37.7e6,
                                                              rel=2e-3)
    # at 32 live slots: about 1.55 TFLOP a step over 32 layers
    assert 32 * 32 * counters.moe_padded_flops(cfg, 1) == \
        pytest.approx(1.55e12, rel=2e-2)


def test_kv_bytes_per_token():
    assert counters.kv_bytes_per_token(_cfg("granite-moe-3b-a800m")) \
        == 64 * 1024
    assert counters.kv_bytes_per_token(_cfg("qwen3-8b-l12")) == 48 * 1024


def test_weight_bytes_match_the_weights_made():
    for name in ("granite-moe-3b-a800m", "qwen3-8b-l12"):
        cfg = _cfg(name)
        made = sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(weights.shapes(cfg)))
        embed = cfg.vocab * cfg.d_model * 2     # a step reads only B rows
        assert counters.weight_bytes(cfg) == made - embed


def test_decode_step_counts():
    cfg = _cfg("qwen3-8b-l12")
    flops, nbytes = counters.decode_step(cfg, [100, 200])
    per_token = [counters.token_flops(cfg, c, True) for c in (100, 200)]
    assert flops == sum(per_token)
    assert nbytes == counters.weight_bytes(cfg) + 2 * 4096 * 2 \
        + 48 * 1024 * 300
    # dense: 2 * (attention projections + MLP) per layer, + the head
    d = 4096
    proj = d * (32 + 16) * 128 + 32 * 128 * d
    assert counters.token_flops(cfg, 1, True) == \
        12 * (2 * proj + 4 * 32 * 128 + 6 * d * 12288) + 2 * d * 151936
    assert counters.prefill_chunk(cfg, 0, 2, True) == \
        counters.token_flops(cfg, 1, False) + \
        counters.token_flops(cfg, 2, True)


def test_expected_experts_touched():
    cfg = _cfg("granite-moe-3b-a800m")
    assert counters.experts_touched(cfg, 1) == pytest.approx(8)
    assert counters.experts_touched(cfg, 32) == pytest.approx(40, abs=0.05)


def test_weights_have_the_programs_layout():
    from repro.models import family_module
    cfg = _cfg("qwen3-8b-l12")
    ours = weights.shapes(cfg)
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
