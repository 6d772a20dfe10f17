"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler refuses what the Pallas interpreter accepts: blocks that
break the (8, 128) tiling rule, vector reshapes Mosaic cannot lay out,
primitives with no Mosaic lowering, programs that do not fit the chip's
HBM.  These tests compile the seven kernels at real model widths and the
serving engine's decode programs at published widths, so such faults show
here instead of on the chip.  Nothing runs: results and times need the chip
(``python chip_smoke.py``).

The topology is described inside a module fixture and nowhere else: only one
process may load the TPU library, and it keeps it until it exits.
"""
import dataclasses
import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.distributed.sharding import named, param_shardings
from repro.kernels import ops
from repro.launch.mesh import make_mesh
from repro.launch.serve import (_mesh_paged_jitted_steps, _paged_jitted_steps,
                                _prefill_cache_specs)
from repro.models import family_module
from repro.models import layers as L

HBM_BYTES = int(15.75 * 2**30)   # one v5e chip, as its compiler counts it

BF16, F32 = jnp.bfloat16, jnp.float32

# (name, op, argument shapes/dtypes, keyword arguments): the widths of the
# models and layers each kernel serves
KERNELS = [
    ("gemm-4096^3", ops.matmul, [((4096, 4096), BF16), ((4096, 4096), BF16)],
     {}),
    ("gemm-qwen3-mlp", ops.matmul,
     [((1000, 4096), BF16), ((4096, 12288), BF16)], {}),
    ("gemv-qwen3-mlp", ops.matvec, [((12288, 4096), BF16), ((4096,), BF16)],
     {}),
    ("dot-16384", ops.dot, [((16384,), BF16), ((16384,), BF16)], {}),
    ("conv2d-resnet50-3x3", ops.conv2d,
     [((64, 58, 58), BF16), ((64, 64, 3, 3), BF16)], {}),
    ("conv2d-resnet50-1x1", ops.conv2d,
     [((64, 56, 56), F32), ((256, 64, 1, 1), F32)], {}),
    ("flash-qwen3", ops.attention,
     [((1, 4096, 32, 128), BF16), ((1, 4096, 8, 128), BF16),
      ((1, 4096, 8, 128), BF16)], {}),
    ("flash-zamba2", ops.attention,
     [((1, 4096, 32, 80), BF16), ((1, 4096, 32, 80), BF16),
      ((1, 4096, 32, 80), BF16)], {}),
    ("rwkv6-3b", ops.rwkv6,
     [((1, 4096, 40, 64), BF16)] * 4 + [((40, 64), BF16)], {}),
    ("mamba2-zamba2", ops.mamba2,
     [((1, 4096, 80, 64), BF16), ((1, 4096, 80), BF16),
      ((1, 4096, 80, 64), BF16), ((1, 4096, 80, 64), BF16)], {}),
]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _structs(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _fits(compiled) -> int:
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert used <= HBM_BYTES, f"{used / 2**30:.2f} GiB > 15.75 GiB"
    return used


@pytest.mark.parametrize("name,op,shapes,kw", KERNELS,
                         ids=[k[0] for k in KERNELS])
def test_kernel_compiles_for_v5e(one_chip, name, op, shapes, kw):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    fn = jax.jit(functools.partial(op, implementation="pallas", **kw))
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


def test_granite_paged_decode_fits_one_chip(one_chip):
    """The smoke's serving setting: 8 slots x 2048 rows, page 16."""
    cfg = get_config("granite-moe-3b-a800m")
    mod = family_module(cfg)
    slots, max_seq = 8, 2048
    params = _structs(jax.eval_shape(functools.partial(mod.init, cfg, tp=1),
                                     jax.random.PRNGKey(0)), one_chip)
    cache = _structs(jax.eval_shape(functools.partial(
        mod.init_paged_cache, cfg, slots, slots * max_seq, max_seq, 1)),
        one_chip)
    decode, _, _ = _paged_jitted_steps(cfg, 1, "xla")
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one_chip)
    compiled = decode.lower(params, cache, i32((slots, 1)), i32((slots,)),
                            i32((slots, max_seq))).compile()
    _fits(compiled)


def test_qwen3_paged_decode_compiles_on_2x2_mesh(topo):
    """qwen3-8b does not fit one chip; at data=1, model=4 each chip holds
    a quarter of its weights and of its KV pool."""
    cfg = get_config("qwen3-8b")
    mod = family_module(cfg)
    mesh = make_mesh((1, 4), ("data", "model"), devices=topo.devices)
    tp, slots, max_seq = 4, 8, 2048
    params = jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        jax.eval_shape(functools.partial(mod.init, cfg, tp=tp),
                       jax.random.PRNGKey(0)),
        param_shardings(mod, cfg, mesh))
    cache = jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        jax.eval_shape(functools.partial(
            mod.init_paged_cache, cfg, slots, slots * max_seq, max_seq, tp)),
        named(mod.paged_cache_specs(cfg), mesh))
    decode, prefill, _ = _mesh_paged_jitted_steps(cfg, tp, "xla", mesh)
    rep = named(jax.sharding.PartitionSpec(), mesh)
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=rep)
    compiled = decode.lower(params, cache, i32((slots, 1)), i32((slots,)),
                            i32((slots, max_seq))).compile()
    _fits(compiled)   # per device

    # a 128-token chunk of a 1024-token prompt's prefill keeps each chip's
    # quarter of the weights: nothing is gathered whole onto one device
    pcache = jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        jax.eval_shape(functools.partial(mod.init_prefill_cache, cfg, 1,
                                         1024, tp)),
        named(_prefill_cache_specs(mod, cfg), mesh))
    compiled = prefill.lower(params, pcache, i32((1, 128)),
                             i32((1,))).compile()
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    assert _fits(compiled) < weights / 2


# the benchmark's three cells: (config, overrides, slots, rows a slot,
# batch); the compile decodes a batch smaller than the cell's, over the
# cell's whole pool, so that the page gather's dense view is not pool-sized
# itself
POOL_CELLS = {
    "granite-moe-3b-a800m.chat": ("granite-moe-3b-a800m", {}, 32, 1280, 8),
    "qwen3-8b-l12.chat": ("qwen3-8b", {"n_layers": 12}, 32, 1280, 8),
    "qwen3-8b-l12.long-decode": ("qwen3-8b", {"n_layers": 12}, 5, 12288, 4),
}
# ops that copy or write back; one the size of the pool or of one layer's
# pool copies, relays out or restacks the pool
_WRITES = ("copy", "copy-start", "copy-done", "dynamic-update-slice")
_BF16_OP = re.compile(r"%[\w.\-]+ = bf16\[([\d,]*)\]\{[^}]*\} ([\w\-]+)\(")


@pytest.mark.parametrize("cell", list(POOL_CELLS))
def test_paged_decode_updates_the_pool_in_place(one_chip, cell):
    """The batched decode of each cell, at the cell's widths, depth and
    pool, writes the stacked pool where it lies: no copy or update-slice
    the size of the pool or of one layer's pool (in the loop or around
    it), the pool aliased from argument to result.  The one pool-sized
    read is the layer slice that the row gather reads from, in the chat
    cells, whose layer pools are staged (``layers.STAGE_POOL_BYTES``): one
    a K or V leaf, and temporaries under two layers' pool.  Long-decode's
    larger layer pools are gathered straight from the stacked leaves: no
    slice, and temporaries under one layer's pool."""
    arch, over, slots, max_seq, batch = POOL_CELLS[cell]
    cfg = dataclasses.replace(get_config(arch), **over)
    mod = family_module(cfg)
    params = _structs(jax.eval_shape(functools.partial(mod.init, cfg, tp=1),
                                     jax.random.PRNGKey(0)), one_chip)
    cache = _structs(jax.eval_shape(functools.partial(
        mod.init_paged_cache, cfg, slots, slots * max_seq, max_seq, 1)),
        one_chip)
    decode, _, _ = _paged_jitted_steps(cfg, 1, "xla")
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one_chip)
    compiled = decode.lower(params, cache, i32((batch, 1)), i32((batch,)),
                            i32((batch, max_seq))).compile()

    leaves = jax.tree_util.tree_leaves(cache)
    pool_bytes = sum(x.size * x.dtype.itemsize for x in leaves)
    stacked = {x.size for x in leaves}
    layer = {x.size // cfg.n_layers for x in leaves}
    ops = [(m.group(0), m.group(2),
            math.prod(map(int, filter(None, m.group(1).split(",")))))
           for m in _BF16_OP.finditer(compiled.as_text())]
    written = [t for t, op, n in ops
               if op in _WRITES and n in stacked | layer]
    assert not written, written
    read = [t for t, op, n in ops if op == "dynamic-slice"
            and n in stacked | layer]
    staged = [x for x in leaves if x.size // cfg.n_layers * x.dtype.itemsize
              <= L.STAGE_POOL_BYTES]
    assert len(staged) in (0, len(leaves)), staged
    assert len(read) == len(staged) and not any(
        n in stacked for _, op, n in ops if op == "dynamic-slice"), read
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= pool_bytes
    assert ma.temp_size_in_bytes < (2 if staged else 1) * pool_bytes \
        / cfg.n_layers
