import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (the (e) deliverable, DESIGN.md §6/§7).

For every (architecture × input-shape) cell, ``lower().compile()`` the
appropriate step function on the single-pod 16×16 mesh AND the 2×16×16
multi-pod mesh, proving the distribution config is coherent: shardings
resolve, collectives lower, and the per-device memory fits.  Records per
cell: memory_analysis, cost_analysis aggregates, and HLO-derived dot-FLOPs /
collective bytes (repro.launch.hlo_analysis — while-trip-aware, since XLA's
own cost analysis counts scan bodies once).

The two env lines above MUST stay first — jax locks the device count on
first init.  Nothing outside this launcher sees 512 devices.

Usage:
  python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all          # every runnable cell, both meshes
"""
import argparse
import dataclasses
import functools
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import (ARCH_IDS, cell_skip_reason, get_config,
                           runnable_cells)
from repro.distributed.context import DEFAULT_TRAIN_SPEC, set_activation_spec
from repro.distributed.sharding import batch_specs, named, prune_specs
from repro.launch import hlo_analysis
from repro.launch.mesh import describe, make_production_mesh
from repro.launch.steps import make_decode_step, make_prefill_step, \
    make_train_step
from repro.models import family_module
from repro.models.config import SHAPES, ModelConfig, ShapeCell
from repro.optim import AdamW

ART_DIR = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def input_structs(cfg: ModelConfig, shape: ShapeCell) -> dict:
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    b, s = shape.global_batch, shape.seq_len
    f32 = jnp.dtype(cfg.dtype)
    if cfg.embed_inputs:   # hubert: precomputed frame embeddings (stub)
        d = {"frames": jax.ShapeDtypeStruct((b, s, cfg.d_model), f32)}
    elif cfg.vis_tokens:   # internvl2: patch-embedding prefix (stub)
        st = s - cfg.vis_tokens
        d = {"tokens": jax.ShapeDtypeStruct((b, st), jnp.int32),
             "patches": jax.ShapeDtypeStruct((b, cfg.vis_tokens, cfg.d_model),
                                             f32)}
    else:
        d = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    if shape.kind == "train":
        lbl = s - cfg.vis_tokens if cfg.vis_tokens else s
        d["labels"] = jax.ShapeDtypeStruct((b, lbl), jnp.int32)
    return d


def _batch_axes_for(batch: int, mesh) -> tuple[str, ...]:
    """Shard the batch over mesh axes whose product divides it."""
    axes = []
    prod = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names and batch % (prod * mesh.shape[a]) == 0:
            axes.append(a)
            prod *= mesh.shape[a]
    return tuple(axes)


def _puredp_specs(tree):
    """Map TP specs to pure-FSDP: 'model' joins the FSDP ('data') axis on the
    weight dim; nothing is tensor-parallel."""
    from jax.sharding import PartitionSpec as P

    def entry(e):
        if e == "model":
            return None
        if e == "data":
            return ("data", "model")
        if isinstance(e, tuple):
            out = []
            for a in e:
                if a == "model":
                    continue
                out.append(a)
            if "data" in out:
                out.append("model")
            return tuple(out) if len(out) > 1 else (out[0] if out else None)
        return e

    def one(spec: P) -> P:
        return P(*(entry(e) for e in spec))

    return jax.tree_util.tree_map(one, tree,
                                  is_leaf=lambda x: isinstance(x, P))


def build_cell(cfg: ModelConfig, shape: ShapeCell, mesh, impl: str = "xla",
               mode: str = "fsdp"):
    """Returns (fn, example_args, in_shardings, out_shardings, donate).
    mode: 'fsdp'   — weights sharded over data+model, TP over model (baseline)
          'zero1'  — weights TP-only, optimizer moments data-sharded
          'puredp' — no TP at all: tp=1 (exact configs, no head padding),
                     weights/moments FSDP over data×model, batch over the
                     whole mesh.  The qwen3 hillclimb winner for mid-size
                     models (EXPERIMENTS.md §Perf)."""
    from jax.sharding import PartitionSpec as P

    tp = 1 if mode == "puredp" else mesh.shape["model"]
    mod = family_module(cfg)
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(functools.partial(mod.init, cfg, tp=tp), key)
    pspecs = mod.specs(cfg)
    if mode == "zero1":
        from repro.distributed.sharding import zero1_specs
        p_sh = named(zero1_specs(pspecs), mesh)
    elif mode == "puredp":
        pspecs = _puredp_specs(pspecs)
        p_sh = named(pspecs, mesh)
    else:
        p_sh = named(pspecs, mesh)

    baxes = _batch_axes_for(shape.global_batch, mesh)
    if mode == "puredp":
        if shape.global_batch % mesh.size == 0:
            baxes = tuple(mesh.axis_names)
        else:
            baxes = baxes  # fall back: divisibility decides
    bspecs = {k: P(baxes, *list(v)[1:]) for k, v in batch_specs(cfg).items()}
    batch = input_structs(cfg, shape)
    b_sh = named({k: bspecs[k] for k in batch}, mesh)

    if shape.kind == "train":
        opt = AdamW()
        opt_state = jax.eval_shape(opt.init, params)
        o_sh = named(opt.init_specs(pspecs), mesh)  # moments stay sharded
        fn = make_train_step(cfg, opt, tp=tp, impl=impl)
        return (fn, (params, opt_state, batch), (p_sh, o_sh, b_sh),
                (p_sh, o_sh, None), (0, 1))

    if shape.kind == "prefill":
        fn = make_prefill_step(cfg, tp=tp, impl=impl)
        return fn, (params, batch), (p_sh, b_sh), None, ()

    # decode: one new token against a seq_len-deep cache
    cache = jax.eval_shape(functools.partial(
        mod.init_cache, cfg, shape.global_batch, shape.seq_len, tp))
    c_specs = prune_specs(mod.cache_specs(cfg), mesh)
    # respect the batch divisibility rule on cache batch dims too
    c_specs = jax.tree_util.tree_map(
        lambda sp: P(*[(baxes if e in (("pod", "data"), "data") else e)
                       for e in sp]), c_specs,
        is_leaf=lambda x: isinstance(x, P))
    c_sh = named(c_specs, mesh)
    toks = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    t_sh = named(P(baxes, None), mesh)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    fn = make_decode_step(cfg, tp=tp, impl=impl)
    return (fn, (params, cache, toks, pos),
            (p_sh, c_sh, t_sh, None), (None, c_sh), (1,))


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               layers: int | None = None, save_hlo: bool = False,
               impl: str = "xla", variant: str = "",
               mode: str = "fsdp") -> dict:
    cfg = get_config(arch)
    if layers:
        cfg = dataclasses.replace(cfg, n_layers=layers)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    if mode == "puredp":
        from jax.sharding import PartitionSpec as P
        set_activation_spec(P(("pod", "data", "model"), None, None), mesh)
    else:
        set_activation_spec(DEFAULT_TRAIN_SPEC, mesh)
    t0 = time.time()
    fn, args, in_sh, out_sh, donate = build_cell(cfg, shape, mesh, impl, mode)
    with jax.set_mesh(mesh):
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    set_activation_spec(None)

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    rep = hlo_analysis.analyze(hlo)
    n_dev = mesh.size

    record = {
        "arch": arch, "shape": shape_name, "mesh": describe(mesh),
        "n_devices": n_dev, "kind": shape.kind,
        "n_layers": cfg.n_layers, "variant": variant or "baseline",
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
        },
        "cost_analysis": {
            "flops_scan_once": ca.get("flops", 0.0),
            "bytes_accessed_scan_once": ca.get("bytes accessed", 0.0),
        },
        "hlo": {
            "dot_flops": rep.dot_flops,
            "collective_bytes": rep.collective_bytes,
            "collective_counts": rep.n_collectives,
            "group_sizes": rep.group_sizes,
            "wire_bytes": rep.wire_bytes(),
            "text_bytes": len(hlo),
        },
    }
    if save_hlo:
        ART_DIR.mkdir(parents=True, exist_ok=True)
        tag = f"{arch}_{shape_name}_{describe(mesh)}{variant}"
        (ART_DIR / f"{tag}.hlo").write_text(hlo)
    return record


def save_record(record: dict) -> Path:
    ART_DIR.mkdir(parents=True, exist_ok=True)
    tag = (f"{record['arch']}_{record['shape']}_{record['mesh']}"
           + ("" if record["variant"] == "baseline"
              else f"_{record['variant']}"))
    path = ART_DIR / f"{tag}.json"
    path.write_text(json.dumps(record, indent=1))
    return path


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="every runnable cell on both meshes")
    ap.add_argument("--layers", type=int, default=None,
                    help="override n_layers (roofline extrapolation probes)")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--variant", default="", help="tag for perf experiments")
    ap.add_argument("--mode", default="fsdp", choices=("fsdp", "zero1", "puredp"),
                    help="train-cell weight sharding strategy")
    args = ap.parse_args()

    if args.all:
        cells = [(a, s, m) for a, s in runnable_cells()
                 for m in (False, True)]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        reason = cell_skip_reason(args.arch, args.shape)
        if reason:
            print(f"SKIP {args.arch} x {args.shape}: {reason}")
            return
        meshes = (False, True) if args.both_meshes else (args.multi_pod,)
        cells = [(args.arch, args.shape, m) for m in meshes]

    failures = 0
    for arch, shape, multi in cells:
        tag = f"{arch} x {shape} x {'2x16x16' if multi else '16x16'}"
        try:
            rec = lower_cell(arch, shape, multi_pod=multi,
                             layers=args.layers, save_hlo=args.save_hlo,
                             variant=args.variant, mode=args.mode)
            path = save_record(rec)
            m = rec["memory"]
            print(f"OK   {tag}: compile {rec['compile_s']}s  "
                  f"arg {m['argument_bytes']/2**30:.2f}GiB  "
                  f"temp {m['temp_bytes']/2**30:.2f}GiB  "
                  f"dotF {rec['hlo']['dot_flops']:.3e}  "
                  f"wire {rec['hlo']['wire_bytes']:.3e}B -> {path.name}")
        except Exception as e:  # a failure here is a bug in our system
            failures += 1
            print(f"FAIL {tag}: {type(e).__name__}: {e}")
            traceback.print_exc(limit=3)
    if failures:
        raise SystemExit(f"{failures} dry-run cells failed")


if __name__ == "__main__":
    main()
