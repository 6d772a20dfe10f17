"""Distribution substrate: spec pruning, batch specs, activation-sharding
context, and multi-device pipeline parallelism / elastic restore via a
subprocess that widens the host platform."""
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed import context
from repro.distributed.context import (DEFAULT_TRAIN_SPEC, activation_spec,
                                       constrain_activations,
                                       get_activation_spec,
                                       set_activation_spec)
from repro.distributed.sharding import batch_specs, named, prune_specs
from repro.launch.mesh import make_mesh

SRC = str(Path(__file__).resolve().parents[1] / "src")


def test_prune_specs_drops_missing_axes():
    mesh = make_mesh((1, 1), ("data", "model"))
    tree = {"a": P(("pod", "data"), "model"), "b": P("pod"), "c": P(None)}
    got = prune_specs(tree, mesh)
    assert got["a"] == P("data", "model")
    assert got["b"] == P(None)
    assert got["c"] == P(None)


def test_named_builds_shardings():
    mesh = make_mesh((1, 1), ("data", "model"))
    sh = named({"w": P("model", "data")}, mesh)
    assert sh["w"].mesh.shape == {"data": 1, "model": 1}


def test_batch_specs_families():
    from repro.configs import get_config
    assert "frames" in batch_specs(get_config("hubert-xlarge"))
    assert "patches" in batch_specs(get_config("internvl2-76b"))
    assert set(batch_specs(get_config("qwen3-8b"))) == {"tokens", "labels"}


def test_activation_context_noop_when_unset():
    import jax.numpy as jnp
    set_activation_spec(None)
    x = jnp.ones((2, 4, 8))
    assert constrain_activations(x) is x


def test_activation_spec_context_manager_scopes_and_restores():
    mesh = make_mesh((1, 1), ("data", "model"))
    set_activation_spec(P("data", None, None), mesh)
    with activation_spec(DEFAULT_TRAIN_SPEC, mesh):
        # pruned against the install mesh's axes ('pod' dropped)
        assert get_activation_spec() == P("data", "model", None)
    assert get_activation_spec() == P("data", None, None)   # restored
    context.reset()
    assert get_activation_spec() is None


def test_activation_spec_installed_without_mesh_prunes_lazily():
    """Regression: ``set_activation_spec(spec)`` with no mesh used to store
    the raw spec, and ``constrain_activations`` then crashed on any mesh
    lacking the 'pod' axis DEFAULT_TRAIN_SPEC names.  The spec must prune
    at apply time against the mesh actually active."""
    import jax.numpy as jnp
    set_activation_spec(DEFAULT_TRAIN_SPEC)   # no mesh: raw spec stored
    mesh = make_mesh((1, 1), ("data", "model"))   # podless
    x = jnp.ones((2, 4, 8))
    with jax.set_mesh(mesh):
        out = jax.jit(constrain_activations)(x)
    assert out.shape == x.shape
    assert float(out.sum()) == float(x.sum())


def test_activation_context_fixture_installs():
    # paired with the test below: relies on pytest's in-file definition
    # order to verify the conftest autouse fixture resets between tests
    set_activation_spec(DEFAULT_TRAIN_SPEC)
    assert get_activation_spec() is not None


def test_activation_context_fixture_isolates():
    assert get_activation_spec() is None


def test_make_host_mesh_tp_factors_device_count():
    from repro.launch.mesh import make_host_mesh
    n = len(jax.devices())
    mesh = make_host_mesh(tp=n)
    assert mesh.shape["model"] == n and mesh.shape["data"] == 1
    assert dict(make_host_mesh().shape) == {"data": n, "model": 1}
    with pytest.raises(ValueError):
        make_host_mesh(tp=n + 1)   # n + 1 never divides n
    with pytest.raises(ValueError):
        make_host_mesh(tp=0)


_MULTIDEV = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "__SRC__")
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    # --- pipeline parallelism over 4 stages -----------------------------
    from repro.distributed.pipeline import pipeline_apply
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ("stage",))
    S, B, D = 4, 8, 16
    key = jax.random.PRNGKey(0)
    ws = jax.random.normal(key, (S, D, D)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(1), (B, D))

    def layer(w, h):
        return jnp.tanh(h @ w)

    got = pipeline_apply(layer, ws, x, mesh=mesh, axis="stage")
    want = x
    for i in range(S):
        want = layer(ws[i], want)
    assert np.allclose(np.asarray(got), np.asarray(want), atol=1e-5), \\
        float(np.max(np.abs(np.asarray(got) - np.asarray(want))))
    print("pipeline OK")

    # --- elastic checkpoint restore across mesh shapes --------------------
    from repro.ft import CheckpointManager
    import tempfile
    tmp = tempfile.mkdtemp()
    mgr = CheckpointManager(tmp)
    mesh8 = make_mesh((8, 1), ("data", "model"))
    sharding = jax.sharding.NamedSharding(mesh8, P("data", None))
    arr = jax.device_put(jnp.arange(32.0).reshape(8, 4), sharding)
    mgr.save(1, {"w": arr})
    mesh2 = make_mesh((2, 1), ("data", "model"))
    got = mgr.restore(1, like={"w": jax.ShapeDtypeStruct((8, 4), jnp.float32)},
                      mesh=mesh2, specs={"w": P("data", None)})
    assert got["w"].sharding.mesh.shape["data"] == 2
    assert np.allclose(np.asarray(got["w"]), np.arange(32.0).reshape(8, 4))
    print("elastic OK")

    # --- quantized/bf16 DP reduction path runs under shard_map ------------
    from jax.experimental.shard_map import shard_map
    def psum_bf16(g):
        return jax.lax.psum(g.astype(jnp.bfloat16), "data").astype(jnp.float32)
    f = shard_map(psum_bf16, mesh=mesh8, in_specs=P("data"), out_specs=P())
    r = f(jnp.ones((8, 4)))
    assert np.allclose(np.asarray(r), 8.0, atol=0.1)
    print("bf16 reduce OK")
""")


def test_multidevice_pipeline_and_elastic():
    script = _MULTIDEV.replace("__SRC__", SRC)
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "pipeline OK" in proc.stdout
    assert "elastic OK" in proc.stdout
    assert "bf16 reduce OK" in proc.stdout


_MESH_FAMILIES = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "__SRC__")
    import dataclasses
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.configs import get_config
    from repro.distributed.sharding import named, param_shardings, \\
        prune_specs
    from repro.launch.mesh import make_host_mesh
    from repro.models import family_module, reduced

    mesh = make_host_mesh(tp=4)
    assert dict(mesh.shape) == {"data": 2, "model": 4}
    axes = set(mesh.axis_names)

    def spec_leaves(tree):
        return jax.tree_util.tree_leaves(
            tree, is_leaf=lambda x: isinstance(x, P))

    # every leaf of every family's specs prunes to the mesh's axes and the
    # real (tp-padded) arrays actually lay out on (data=2, model=4)
    for arch in ("qwen3-8b", "gemma2-2b", "zamba2-2.7b", "rwkv6-3b"):
        cfg = reduced(get_config(arch))
        mod = family_module(cfg)
        for tree in (mod.specs(cfg), mod.cache_specs(cfg),
                     mod.paged_cache_specs(cfg)):
            pruned = spec_leaves(prune_specs(tree, mesh))
            assert pruned, arch
            for spec in pruned:
                for entry in spec:
                    names = entry if isinstance(entry, tuple) else (entry,)
                    assert all(nm is None or nm in axes for nm in names), \\
                        (arch, spec)
        params = jax.device_put(
            mod.init(cfg, jax.random.PRNGKey(0), tp=4),
            param_shardings(mod, cfg, mesh))
        dense = jax.device_put(mod.init_cache(cfg, 4, 32, 4),
                               named(mod.cache_specs(cfg), mesh))
        paged = jax.device_put(mod.init_paged_cache(cfg, 4, 32, 32, 4),
                               named(mod.paged_cache_specs(cfg), mesh))
        jax.block_until_ready((params, dense, paged))
        print(arch, "layout OK")

    # sharded-vs-dense teacher-forced decode oracle (qwen3, f32 so the
    # collective's reassociation drift stays far below top-2 logit gaps)
    from repro.launch.steps import make_decode_step
    cfg = dataclasses.replace(reduced(get_config("qwen3-8b")),
                              dtype="float32")
    mod = family_module(cfg)
    params = mod.init(cfg, jax.random.PRNGKey(0), tp=4)
    step = jax.jit(make_decode_step(cfg, tp=4, impl="xla"))
    prompt = np.random.default_rng(1).integers(
        0, cfg.vocab, size=(2, 6)).astype(np.int32)

    def rollout(p, cache):
        outs = []
        for t in range(prompt.shape[1]):
            logits, cache = step(p, cache, jnp.asarray(prompt[:, t:t + 1]),
                                 jnp.int32(t))
            outs.append(np.asarray(logits[:, -1], np.float64))
        return np.stack(outs)

    ref = rollout(params, mod.init_cache(cfg, 2, 8, 4))
    got = rollout(
        jax.device_put(params, param_shardings(mod, cfg, mesh)),
        jax.device_put(mod.init_cache(cfg, 2, 8, 4),
                       named(mod.cache_specs(cfg), mesh)))
    err = float(np.abs(ref - got).max())
    assert err < 1e-3, err
    assert (ref.argmax(-1) == got.argmax(-1)).all()
    print("oracle OK", err)
""")


def test_mesh_layout_all_families_and_decode_oracle():
    script = _MESH_FAMILIES.replace("__SRC__", SRC)
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    for arch in ("qwen3-8b", "gemma2-2b", "zamba2-2.7b", "rwkv6-3b"):
        assert f"{arch} layout OK" in proc.stdout
    assert "oracle OK" in proc.stdout
