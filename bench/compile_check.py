"""Compile each cell's serving programs for a TPU v5e, with no chip attached.

    JAX_PLATFORMS=cpu PYTHONPATH=src python bench/compile_check.py [cell ...]

For every cell of ``BENCHMARK.json`` (or those named), the engine's own
jitted programs are compiled at the cell's sizes for one described v5e chip:
the batched decode step, one prefill chunk against the private cache of the
longest prompt the mix sends, and the commit of that prompt into the pool.
It prints each program's ``memory_analysis`` and the weights and KV pool that
live beside it.  Nothing runs: this bounds memory, it measures no time.
"""
from __future__ import annotations

import functools
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

GIB = 2 ** 30


def _bytes(tree) -> int:
    import jax
    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))


def check(name: str, one_chip) -> None:
    import jax
    import jax.numpy as jnp

    from bench import spec, weights
    from repro.launch.serve import _paged_jitted_steps
    from repro.models import family_module

    cell = spec.load_cell(name)
    cfg = spec.model_config(cell.config)
    mod = family_module(cfg)
    e = cell.engine
    slots, max_seq = e["slots"], e["max_seq"]
    longest = max(cell.traffic["prompt"]["grid"])

    def structs(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)

    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one_chip)
    params = structs(weights.shapes(cfg))
    cache = structs(jax.eval_shape(functools.partial(
        mod.init_paged_cache, cfg, slots, slots * max_seq, max_seq, 1)))
    pcache = structs(jax.eval_shape(functools.partial(
        mod.init_prefill_cache, cfg, 1, longest, 1)))
    decode, write_slot, _ = _paged_jitted_steps(cfg, 1, "xla")
    programs = {
        "decode": decode.lower(params, cache, i32((slots, 1)), i32((slots,)),
                               i32((slots, max_seq))),
        f"prefill_chunk@{longest}": decode.lower(
            params, pcache, i32((1, e["prefill_chunk"])), i32((1,))),
        f"commit@{longest}": write_slot.lower(cache, pcache, i32(()),
                                              i32((longest,))),
    }
    print(f"{name}: weights {_bytes(params) / GIB:.3f} GiB, KV pool "
          f"{_bytes(cache) / GIB:.3f} GiB, private prefill cache "
          f"{_bytes(pcache) / GIB:.3f} GiB", flush=True)
    for prog, lowered in programs.items():
        ma = lowered.compile().memory_analysis()
        peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        print(f"  {prog}: arguments {ma.argument_size_in_bytes / GIB:.3f} "
              f"GiB, outputs {ma.output_size_in_bytes / GIB:.3f} GiB, "
              f"temporaries {ma.temp_size_in_bytes / GIB:.3f} GiB, aliased "
              f"{ma.alias_size_in_bytes / GIB:.3f} GiB -> "
              f"{peak / GIB:.3f} GiB while it runs", flush=True)


def main(argv: list[str]) -> int:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import spec

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    names = argv or [w["name"] for w in spec.benchmark()["workloads"]]
    for name in names:
        check(name, one_chip)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
