"""Fault tolerance (checkpoint/watchdog), data pipeline, optimizer and
gradient compression."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.pipeline import Prefetcher, SyntheticLM
from repro.ft import CheckpointManager, Watchdog
from repro.optim import AdamW, cosine_schedule
from repro.optim.compression import ef_compress, ef_init


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def _tree():
    return {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"w": jnp.ones((4,), jnp.bfloat16) * 1.5,
                  "step": jnp.int32(7)}}


def test_checkpoint_roundtrip_bf16(tmp_path):
    mgr = CheckpointManager(tmp_path)
    tree = _tree()
    mgr.save(3, tree)
    like = jax.tree_util.tree_map(lambda x: jnp.zeros_like(x), tree)
    got = mgr.restore(3, like=like)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(tree)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_checkpoint_retention_and_latest(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, _tree())
    assert mgr.all_steps() == [3, 4]
    assert mgr.latest_step() == 4


def test_checkpoint_async_then_wait(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save(9, _tree(), blocking=False)
    mgr.wait()
    assert mgr.latest_step() == 9


def test_checkpoint_atomic_no_tmp_left(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save(5, _tree())
    assert not list(tmp_path.glob("tmp-*"))


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------

def test_watchdog_detects_dead_and_stragglers():
    wd = Watchdog(n_workers=4, dead_after_s=10.0, straggler_factor=2.0,
                  cordon_after=2)
    now = 1000.0
    for step in range(6):
        for w in range(3):  # worker 3 never beats -> dead
            dt = 1.0 if w != 1 else (5.0 if step >= 3 else 1.0)
            wd.beat(w, step, now=now + step, step_time_s=dt)
    health = wd.check(now=now + 6)
    assert health["dead"] == [3]
    assert 1 in health["cordoned"] or 1 in health["stragglers"]
    assert 0 not in health["stragglers"]


def test_watchdog_elastic_target():
    wd = Watchdog(n_workers=8, dead_after_s=1.0)
    now = 0.0
    for w in range(6):
        wd.beat(w, 0, now=now)
    assert wd.healthy_mesh_size(8, now=0.5) == 4  # 6 healthy -> pow2 = 4


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_data_step_determinism():
    d = SyntheticLM(vocab=100, seq_len=8, global_batch=4, seed=3)
    a, b = d.batch(5), d.batch(5)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = d.batch(6)
    assert not np.array_equal(a["tokens"], c["tokens"])


def test_data_labels_are_shifted_tokens():
    d = SyntheticLM(vocab=100, seq_len=8, global_batch=2, seed=0)
    b = d.batch(0)
    assert b["tokens"].shape == b["labels"].shape == (2, 8)
    assert (b["tokens"] < 100).all() and (b["labels"] < 100).all()


def test_prefetcher_order():
    seen = []

    def fn(step):
        seen.append(step)
        return {"x": step}

    pf = Prefetcher(fn, start_step=2)
    s1, b1 = pf.get()
    s2, b2 = pf.get()
    assert (s1, s2) == (2, 3)
    assert b1["x"] == 2 and b2["x"] == 3


def test_data_global_arrays_shard_over_mesh():
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    d = SyntheticLM(vocab=50, seq_len=4, global_batch=4, seed=1)
    arrs = d.global_arrays(0, mesh)
    assert arrs["tokens"].shape == (4, 4)
    np.testing.assert_array_equal(np.asarray(arrs["tokens"]),
                                  d.batch(0)["tokens"])


# ---------------------------------------------------------------------------
# optimizer + compression
# ---------------------------------------------------------------------------

def test_adamw_minimizes_quadratic():
    opt = AdamW(lr=0.1, weight_decay=0.0, clip_norm=0.0)
    params = {"x": jnp.array([5.0, -3.0])}
    state = opt.init(params)
    for _ in range(150):
        grads = {"x": 2.0 * params["x"]}
        params, state, _ = opt.update(grads, state, params)
    assert float(jnp.abs(params["x"]).max()) < 0.15


def test_adamw_clipping_bounds_update():
    opt = AdamW(lr=1.0, clip_norm=1.0, weight_decay=0.0)
    params = {"x": jnp.zeros(3)}
    state = opt.init(params)
    _, _, gnorm = opt.update({"x": jnp.array([1e4, 0.0, 0.0])}, state, params)
    assert float(gnorm) == pytest.approx(1e4)


def test_cosine_schedule_shape():
    lr = cosine_schedule(1.0, warmup=10, total=100, floor=0.1)
    assert float(lr(jnp.int32(0))) == pytest.approx(0.0)
    assert float(lr(jnp.int32(10))) == pytest.approx(1.0)
    assert float(lr(jnp.int32(100))) == pytest.approx(0.1, abs=1e-3)


def test_ef_compression_error_feedback_unbiased():
    """With constant gradients, EF-int8 compressed sums converge to the true
    sum — the residual never escapes."""
    g = {"w": jnp.asarray(np.linspace(-1, 1, 64), jnp.float32) * 0.37}
    ef = ef_init(g)
    total = jnp.zeros(64)
    for _ in range(50):
        cg, ef = ef_compress(g, ef)
        total = total + cg["w"]
    np.testing.assert_allclose(np.asarray(total), np.asarray(g["w"]) * 50,
                               rtol=2e-2, atol=2e-2)
