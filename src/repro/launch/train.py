"""End-to-end training driver (deliverable (b): the train-kind example).

Runs real steps on whatever devices exist (reduced configs on this CPU
container; the same code path scales to the production mesh — the dry-run
proves those shardings compile).  Features exercised here:

  * deterministic sharded data pipeline with background prefetch,
  * AdamW + clipping + cosine schedule, optional EF-int8 grad compression,
  * atomic/async checkpointing with auto-resume,
  * heartbeat watchdog with straggler accounting,
  * simulated failure injection (--inject-failure-at) to demonstrate the
    checkpoint/restart path.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --smoke \
      --steps 30 --inject-failure-at 12
"""
from __future__ import annotations

import argparse
import functools
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.data.pipeline import Prefetcher, SyntheticLM
from repro.distributed.context import DEFAULT_TRAIN_SPEC, set_activation_spec
from repro.distributed.sharding import batch_specs, named
from repro.ft import CheckpointManager, Watchdog
from repro.launch import compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.launch.steps import make_train_step
from repro.models import family_module, reduced
from repro.optim import AdamW, cosine_schedule
from repro.optim.compression import ef_compress, ef_init


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen3-8b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--checkpoint-dir", default="artifacts/ckpt")
    ap.add_argument("--checkpoint-every", type=int, default=10)
    ap.add_argument("--production-mesh", action="store_true",
                    help="use the 16x16 mesh (requires 256 devices)")
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--inject-failure-at", type=int, default=-1,
                    help="simulate a crash at this step (tests restart)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tuning-db", default=None,
                    help="tuning database (tuner/db.py); defaults to "
                         "artifacts/tuning_db.json")
    ap.add_argument("--tuned-app", default=None,
                    help="co-design app whose tuned kernel blocks to "
                         "install (default: the arch name)")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
    # measured-autotuning pickup (DESIGN.md §8.4): install the app's tuned
    # block shapes as dispatch defaults; shape-exact DB records still win
    from repro.kernels import ops as _ops
    tuned = _ops.configure(app=args.tuned_app or args.arch,
                           db_path=args.tuning_db)
    if tuned:
        print(f"tuned kernel blocks installed: gemm={tuned['gemm']}")
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh())
    tp = mesh.shape.get("model", 1)
    set_activation_spec(DEFAULT_TRAIN_SPEC if tp > 1 else None, mesh)
    mod = family_module(cfg)

    key = jax.random.PRNGKey(args.seed)
    opt = AdamW(lr=cosine_schedule(args.lr, warmup=5, total=args.steps))
    step_fn = make_train_step(cfg, opt, tp=tp)

    p_sh = named(mod.specs(cfg), mesh)
    o_sh = named(opt.init_specs(mod.specs(cfg)), mesh)
    b_sh = named({k: v for k, v in batch_specs(cfg).items()
                  if k in ("tokens", "labels")}, mesh)
    jitted = jax.jit(step_fn, in_shardings=(p_sh, o_sh, b_sh),
                     out_shardings=(p_sh, o_sh, None),
                     donate_argnums=(0, 1))

    ckpt = CheckpointManager(Path(args.checkpoint_dir) / cfg.name)
    start = 0
    latest = ckpt.latest_step()
    state = None
    if latest is not None:
        state = ckpt.restore(latest, like=_eval_state(mod, cfg, opt, key, tp),
                             mesh=mesh, specs=(mod.specs(cfg),
                                               opt.init_specs(mod.specs(cfg))))
    if state is not None:
        params, opt_state = state
        start = latest + 1
        print(f"resumed from checkpoint step {latest}")
    else:
        with jax.set_mesh(mesh):
            params = mod.init(cfg, key, tp=tp)
            opt_state = opt.init(params)

    data = SyntheticLM(cfg.vocab, args.seq_len, args.global_batch, args.seed)
    ef_state = ef_init(params) if args.grad_compression else None
    watchdog = Watchdog(n_workers=jax.process_count())

    fetch = Prefetcher(lambda s: data.batch(s), start_step=start)
    for step in range(start, args.steps):
        got_step, host_batch = fetch.get()
        assert got_step == step
        if cfg.vis_tokens or cfg.embed_inputs:
            host_batch = _adapt_batch(cfg, host_batch)
        batch = {k: jnp.asarray(v) for k, v in host_batch.items()}
        t0 = time.time()
        if step == args.inject_failure_at:
            # drain the async writer first: the injection simulates a crash
            # *after* the last checkpoint landed, so the rerun demonstrably
            # resumes from it (a writer killed mid-write is already safe —
            # it only ever loses the in-flight step, never corrupts)
            ckpt.wait()
            raise SystemExit(
                f"[injected failure at step {step}] — rerun the same "
                f"command; training auto-resumes from the last checkpoint")
        params, opt_state, metrics = jitted(params, opt_state, batch)
        dt = time.time() - t0
        watchdog.beat(jax.process_index(), step, step_time_s=dt)
        if step % 5 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {float(metrics['loss']):.4f}  "
                  f"gnorm {float(metrics['grad_norm']):.3f}  {dt*1e3:.0f}ms  "
                  f"health {watchdog.check()}")
        if step and step % args.checkpoint_every == 0:
            ckpt.save(step, (params, opt_state), blocking=False)
    ckpt.wait()
    ckpt.save(args.steps - 1, (params, opt_state))
    print(f"done; checkpoints in {ckpt.dir}")


def _eval_state(mod, cfg, opt, key, tp):
    params = jax.eval_shape(functools.partial(mod.init, cfg, tp=tp), key)
    return params, jax.eval_shape(opt.init, params)


def _adapt_batch(cfg, batch):
    import numpy as np
    toks, labels = batch["tokens"], batch["labels"]
    if cfg.embed_inputs:   # hubert: frames stand in for the CNN frontend
        rng = np.random.default_rng(int(toks[0, 0]) + 1)
        frames = rng.standard_normal(
            (toks.shape[0], toks.shape[1], cfg.d_model)).astype("float32")
        return {"frames": frames, "labels": labels % cfg.vocab}
    if cfg.vis_tokens:     # internvl2: patch prefix
        rng = np.random.default_rng(int(toks[0, 0]) + 1)
        patches = rng.standard_normal(
            (toks.shape[0], cfg.vis_tokens, cfg.d_model)).astype("float32")
        return {"tokens": toks, "patches": patches, "labels": labels}
    return batch


if __name__ == "__main__":
    main()
