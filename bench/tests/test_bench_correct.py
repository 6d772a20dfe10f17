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

The same toy cell on four chips (``data=1, model=4``, with 4 kv heads so
that the model axis divides every head count) runs in a subprocess that
widens the CPU backend to four devices; and a second family, added as files
under a root of its own, runs end to end.
"""
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench import check, spec, weights
from bench.spec import BENCH, REPO

import bench.run as run_mod

CELL = "toy.chat"
MEAN_LIMIT = 0.004
SAMPLE = 40
DATA = BENCH / "tests" / "data"


def make_root(root, config: dict, *, chips: int = 1, mesh=None,
              family_file=None):
    """A benchmark root holding one toy cell of ``config``, with
    ``family_file`` as its family where the harness has none of that
    name."""
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    for sub in ("configs", "cells", "traffic", "families"):
        (root / "bench" / sub).mkdir(parents=True)
    (root / "bench" / "configs" / "toy.json").write_text(json.dumps(config))
    shutil.copy(family_file or BENCH / "families" / "transformer.py",
                root / "bench" / "families" / f"{config['family']}.py")
    b["configs"] = [{"name": "toy", "source": "toy", "reduced": [],
                     "file": "bench/configs/toy.json", "why": "toy"}]
    b["workloads"] = [{"name": CELL, "config": "toy", "traffic": "toy-chat",
                       "chips": chips, "why": "toy"}]
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
    engine = {"slots": 4, "max_seq": 128, "page_size": 8,
              "prefill_chunk": 16}
    if mesh is not None:
        engine["mesh"] = mesh
    (root / "bench" / "cells" / f"{CELL}.json").write_text(json.dumps({
        "engine": engine, "load": {"rate_per_s": 20.0},
        "correct": {"sample_requests": SAMPLE,
                    "mean_logit_gap": MEAN_LIMIT}}))
    return root


def toy(**config) -> dict:
    """``tests/data/toy.json`` with ``config``'s published keys and the
    program's overrides to match."""
    c = json.loads((DATA / "toy.json").read_text())
    c["config"].update(config)
    if "num_key_value_heads" in config:
        c["overrides"]["n_kv_heads"] = config["num_key_value_heads"]
    return c


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench_root"), toy())


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
    cfg = spec.model_config(cell.config, root=root)
    served, control = check.control_gap(
        cell.family, weights.make(cell.family, cfg, seed),
        cell.family.Dims.of(cell.config), run,
        check.sample(run, seed, SAMPLE), pad_to=128, mode="fp8")
    assert served["mean_logit_gap"] <= MEAN_LIMIT < control["mean_logit_gap"]


def _state_unchanged(engine):
    step = engine._decode_batch

    def broken(params, cache, toks, pos, row_map):
        # the program donates the cache it is given: keep a copy of it
        kept = jax.tree_util.tree_map(jnp.copy, cache)
        return step(params, cache, toks, pos, row_map)[0], kept
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

    c = toy()
    cfg = spec.model_config(c)
    fam = spec.family(c)
    dims = fam.Dims.of(c)
    assert dims.attn_scale == 0.0 and dims.embed_mult == 1.0
    w = weights.make(fam, cfg, 5)
    seqs, at = [jnp.arange(1, 9)], [jnp.arange(8)]
    base = fam.logits(w, dims, seqs, at, pad_to=16)[0]
    scaled = fam.logits(w, dataclasses.replace(dims, logits_div=2.0),
                        seqs, at, pad_to=16)[0]
    assert scaled == pytest.approx(base / 2, rel=1e-5)
    # head_dim ** -0.5 stated as attention_multiplier reads the same
    c["config"]["attention_multiplier"] = 16 ** -0.5
    same = fam.logits(w, fam.Dims.of(c), seqs, at, pad_to=16)[0]
    assert (same == base).all()
    for key, value in (("embedding_multiplier", 12.0),
                       ("residual_multiplier", 0.22),
                       ("tie_word_embeddings", True)):
        c2 = json.loads(json.dumps(c))
        c2["config"][key] = value
        moved = fam.logits(w, fam.Dims.of(c2), seqs, at, pad_to=16)[0]
        assert abs(moved - base).max() > 1e-3, key


# -- what a cell's files must state -------------------------------------------

def test_a_configuration_without_a_family_is_refused(tmp_path):
    c = toy()
    del c["family"]
    with pytest.raises(ValueError, match="'family'"):
        spec.model_config(c)
    c["family"] = "transformer"
    root = make_root(tmp_path, c)
    cfg = json.loads((root / "bench" / "configs" / "toy.json").read_text())
    del cfg["family"]
    (root / "bench" / "configs" / "toy.json").write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match="'family'"):
        spec.load_cell(CELL, root)


@pytest.mark.parametrize("chips,mesh,message", [
    (4, None, "needs a 'mesh'"),
    (4, {"data": 1, "model": 2}, "spans 2 chips"),
    (4, {"data": 1, "tensor": 4}, "must give a whole size"),
    (1, {"data": 1, "model": 1}, "takes no 'mesh'"),
])
def test_a_mesh_must_span_the_cells_chips(tmp_path, chips, mesh, message):
    root = make_root(tmp_path, toy(num_key_value_heads=4), chips=chips,
                     mesh=mesh)
    with pytest.raises(ValueError, match=message):
        spec.load_cell(CELL, root)


def test_a_width_the_model_axis_does_not_divide_is_refused(tmp_path):
    root = make_root(tmp_path, toy(), chips=4, mesh={"data": 1, "model": 4})
    cell = spec.load_cell(CELL, root)
    with pytest.raises(ValueError, match="n_kv_heads=2"):
        spec.model_config(cell.config, cell.model_axis, root)
    cell = spec.load_cell(CELL, make_root(
        tmp_path / "divided", toy(num_key_value_heads=4), chips=4,
        mesh={"data": 1, "model": 4}))
    assert spec.model_config(cell.config, cell.model_axis).n_kv_heads == 4


# -- a family added as files --------------------------------------------------

def test_a_family_added_as_files_runs_end_to_end(tmp_path):
    config = json.loads((DATA / "toy-dense.json").read_text())
    root = make_root(tmp_path, config,
                     family_file=DATA / "toy_family.py")
    assert not (BENCH / "families" / "toydense.py").exists()
    cell = spec.load_cell(CELL, root)
    assert cell.family.__file__ == str(root / "bench" / "families"
                                       / "toydense.py")
    result, _ = _run(root, 1)
    assert result["correct"], result["checks"]
    assert result["checks"]["tokens_compared"]["value"] > 100
    result, _ = _run(root, 1, _token_altered)
    assert not result["correct"]


# -- four chips, on four CPU devices ------------------------------------------

FOUR = """
import json, sys
import jax, numpy as np
import test_bench_correct as t
from pathlib import Path
from bench import spec, weights
from bench.run import cell_mesh

root = t.make_root(Path(sys.argv[1]), t.toy(num_key_value_heads=4), chips=4,
                   mesh={"data": 1, "model": 4})
cell = spec.load_cell(t.CELL, root)
cfg = spec.model_config(cell.config, cell.model_axis, root)
whole = weights.make(cell.family, cfg, 7)
sharded = weights.make(cell.family, cfg, 7,
                       cell_mesh(cell, jax.devices()[:4]))
leaves = jax.tree_util.tree_leaves(sharded)
held = {}
for leaf in leaves:
    for shard in leaf.addressable_shards:
        held[str(shard.device)] = held.get(str(shard.device), 0) \
            + shard.data.nbytes
out = {"devices": len(jax.devices()),
       "equal": all(np.array_equal(np.asarray(a), np.asarray(b))
                    for a, b in zip(jax.tree_util.tree_leaves(whole), leaves)),
       "held": held, "tree_bytes": sum(x.nbytes for x in leaves)}
for name, fault in (("sound", None), ("token_altered", t._token_altered)):
    result, _ = t._run(root, 1, fault)
    out[name] = {"correct": result["correct"], "checks": result["checks"],
                 "count": result["device"]["count"]}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_chips(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(REPO / "src"), str(REPO), str(BENCH / "tests")]))
    proc = subprocess.run(
        [sys.executable, "-c", FOUR, str(tmp_path_factory.mktemp("four"))],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sharded_weights_equal_unsharded_ones(four_chips):
    assert four_chips["devices"] == 4 and four_chips["equal"]


def test_no_device_holds_the_whole_weight_tree(four_chips):
    held = four_chips["held"]
    assert len(held) == 4
    assert max(held.values()) < four_chips["tree_bytes"] / 2


def test_a_four_chip_cell_is_correct(four_chips):
    sound = four_chips["sound"]
    assert sound["count"] == 4
    assert sound["correct"], sound["checks"]


def test_a_four_chip_cell_with_a_token_altered_is_not_correct(four_chips):
    broken = four_chips["token_altered"]
    assert not broken["correct"]
    assert broken["checks"]["mean_logit_gap"]["value"] > MEAN_LIMIT
