"""Compile each cell's serving programs for TPU v5e, with no chip attached.

    JAX_PLATFORMS=cpu PYTHONPATH=src python bench/compile_check.py \
        [--root DIR] [cell ...]

For every cell of ``BENCHMARK.json`` under the root (the repository's, or
``--root``'s), or those named, the engine's own jitted programs are compiled
at the cell's sizes for a described v5e 2x2: on one chip for a one-chip
cell, over the cell's mesh of that many chips for the others.  The programs
are the batched decode step, one prefill chunk against the private cache of
the longest prompt the mix sends, and the commit of that prompt into the
pool.  It prints each program's ``memory_analysis`` and the weights and KV
pool that live beside it, per device.  Nothing runs: this bounds memory, it
measures no time.
"""
from __future__ import annotations

import argparse
import functools
import math
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

GIB = 2 ** 30


def _bytes(tree) -> int:
    """Bytes of ``tree`` on one device: each leaf's shard."""
    import jax
    return sum(math.prod(x.sharding.shard_shape(x.shape)) * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree))


def check(name: str, topo, root: Path) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec, \
        SingleDeviceSharding

    from bench import spec, weights
    from bench.run import cell_mesh
    from repro.distributed.sharding import named, param_shardings
    from repro.launch.serve import _mesh_paged_jitted_steps, \
        _paged_jitted_steps, _prefill_cache_specs
    from repro.models import family_module

    cell = spec.load_cell(name, root)
    cfg = spec.model_config(cell.config, cell.model_axis, root)
    mod = family_module(cfg)
    e = cell.engine
    slots, max_seq, tp = e["slots"], e["max_seq"], cell.model_axis
    longest = max(cell.traffic["prompt"]["grid"])
    mesh = cell_mesh(cell, topo.devices[:cell.chips])
    shapes = weights.shapes(cell.family, cfg)
    if mesh is None:
        replicated = SingleDeviceSharding(topo.devices[0])

        def laid_out(tree, specs):
            return jax.tree_util.tree_map(lambda _: replicated, tree)
        # the chunked prefill compiled as the decode program on a private
        # cache
        decode, write_slot, _ = _paged_jitted_steps(cfg, tp, "xla")
        prefill, param_sh = decode, laid_out(shapes, None)
    else:
        replicated = NamedSharding(mesh, PartitionSpec())

        def laid_out(tree, specs):
            return named(specs, mesh)
        decode, prefill, write_slot = _mesh_paged_jitted_steps(cfg, tp, "xla",
                                                               mesh)
        param_sh = param_shardings(mod, cfg, mesh)

    def structs(tree, shardings):
        return jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, shardings)

    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=replicated)
    params = structs(shapes, param_sh)
    pool = jax.eval_shape(functools.partial(
        mod.init_paged_cache, cfg, slots, slots * max_seq, max_seq, tp))
    cache = structs(pool, laid_out(pool, mod.paged_cache_specs(cfg)))
    private = jax.eval_shape(functools.partial(
        mod.init_prefill_cache, cfg, 1, longest, tp))
    pcache = structs(private, laid_out(private,
                                       _prefill_cache_specs(mod, cfg)))
    programs = {
        "decode": decode.lower(params, cache, i32((slots, 1)), i32((slots,)),
                               i32((slots, max_seq))),
        f"prefill_chunk@{longest}": prefill.lower(
            params, pcache, i32((1, e["prefill_chunk"])), i32((1,))),
        f"commit@{longest}": write_slot.lower(cache, pcache, i32(()),
                                              i32((longest,))),
    }
    where = "" if mesh is None else \
        f" (per device, {cell.chips} devices, mesh {cell.mesh})"
    print(f"{name}: weights {_bytes(params) / GIB:.3f} GiB, KV pool "
          f"{_bytes(cache) / GIB:.3f} GiB, private prefill cache "
          f"{_bytes(pcache) / GIB:.3f} GiB{where}", flush=True)
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

    from bench import spec

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--root", type=Path, default=spec.REPO,
                    help="the benchmark root that holds BENCHMARK.json")
    ap.add_argument("cells", nargs="*")
    args = ap.parse_args(argv)
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    names = args.cells or [w["name"] for w in
                           spec.benchmark(args.root)["workloads"]]
    for name in names:
        check(name, topo, args.root)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
