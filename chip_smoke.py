"""Drive the system's main paths once on a TPU and check what comes out.

    python chip_smoke.py              # one chip: kernels, serving, co-design
    python chip_smoke.py --chips 4    # four chips: qwen3-8b paged serving at
                                      # data=1, model=4, against forward()

Phases (default, one chip, one process):

1. kernels — the seven Pallas kernels of ``repro.kernels.ops`` compiled for
   the chip (``implementation="pallas"``) at the widths of the models and
   layers they serve, each against its ``kernels/ref.py`` oracle;
2. serving — granite-moe-3b-a800m at published widths through the paged
   engine (``PagedServeEngine``, as ``python -m repro.launch.serve --paged``
   runs it): 8 slots, page 16, max_seq 2048, prefill chunk 128, 8 seeded
   requests of 128-1024 prompt tokens and 32 new tokens each.  One
   request's decode logits are compared with ``forward`` over its prompt
   plus generated tokens, on the same chip;
3. co-design — ``codesign(measure=True, measure_backend="pallas")`` over
   granite-moe's own GEMMs, with a tuning database of its own.

Weights and inputs are random, made from ``--seed``.  Every comparison
prints on its own line.  The last line of standard output is one JSON object
``{"ok": true, "device": {...}}``, printed only when every phase passed; the
script exits non-zero without it when JAX finds no TPU, when a phase raises
or when a comparison fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import sys
import tempfile
import time
import traceback
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

SLOTS, MAX_SEQ, PAGE, CHUNK = 8, 2048, 16, 128
N_REQUESTS, MAX_NEW, PROMPT_LENGTHS = 8, 32, (128, 1025)

# Serving logits are bf16 (8-bit mantissa) on both sides.  The engine reaches
# a position through chunked prefill and paged decode, forward() through one
# whole-sequence pass: the same math in other orders, each re-rounded to bf16
# in every one of the model's layers.  The tolerance is a share of the
# reference logits' std, which random init sets per model (0.02 * sqrt(d_model):
# 0.78 for granite, 1.28 for qwen3).  A wrong page, position or stale KV row
# moves logits by about one std on average and several at the maximum;
# rounding moved granite's by 0.023 std on average and 0.26 at the maximum
# over 31 x 49155 logits on a v5e.
LOGIT_TOL_STD = 0.5


def _fail(msg: str) -> None:
    raise AssertionError(msg)


def _rel_err(got, want) -> float:
    import numpy as np
    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    if g.shape != w.shape:
        _fail(f"shape {g.shape} != oracle shape {w.shape}")
    if not np.isfinite(g).all():
        _fail("non-finite output")
    return float(np.max(np.abs(g - w)) / max(np.max(np.abs(w)), 1e-30))


# ---------------------------------------------------------------------------
# phase 1: kernels
# ---------------------------------------------------------------------------

def kernel_cases():
    """(name, kernel, oracle, argument makers, tolerance, reason).  Errors
    are max|kernel - oracle| / max|oracle|; inputs are bf16."""
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    def normal(*shape, scale=1.0):
        return ("normal", shape, scale)

    def log_decay(*shape, scale=0.5):
        return ("log_decay", shape, scale)

    exact = ("bf16 products are exact on both sides, f32 sums differ only "
             "in order, each output is rounded once to bf16 (2^-8)")
    rec = ("f32 recurrences; the kernel's MXU matmuls may round operands "
           "to bf16 (2^-8) where the oracle runs at highest precision")
    return [
        ("gemm 4096x4096x4096", ops.matmul, ref.gemm_ref,
         [normal(4096, 4096), normal(4096, 4096)], 1e-2, exact),
        ("gemm 1000x12288x4096", ops.matmul, ref.gemm_ref,
         [normal(1000, 4096), normal(4096, 12288)], 1e-2, exact),
        ("gemv 12288x4096", ops.matvec, ref.gemv_ref,
         [normal(12288, 4096), normal(4096)], 1e-2, exact),
        ("dot 16384", ops.dot, ref.dot_ref,
         [normal(16384), normal(16384)], 1e-3,
         "f32 sums of 16384 exact products in two orders"),
        ("conv2d resnet50 64x64x56x56 3x3", ops.conv2d, ref.conv2d_ref,
         [normal(64, 58, 58), normal(64, 64, 3, 3)], 1e-2, exact),
        ("flash_attention qwen3 1x4096 32q/8kv hd128", ops.attention,
         ref.attention_ref,
         [normal(1, 4096, 32, 128), normal(1, 4096, 8, 128),
          normal(1, 4096, 8, 128)], 2e-2,
         "f32 softmax on both sides; bf16 output (2^-8) and probabilities "
         "rounded to bf16 in one side's P.V matmul"),
        ("flash_attention zamba2 1x4096 32q/32kv hd80", ops.attention,
         ref.attention_ref,
         [normal(1, 4096, 32, 80), normal(1, 4096, 32, 80),
          normal(1, 4096, 32, 80)], 2e-2,
         "as for qwen3 widths"),
        ("rwkv6 rwkv6-3b 1x4096x40x64", ops.rwkv6, ref.rwkv6_ref,
         [normal(1, 4096, 40, 64), normal(1, 4096, 40, 64),
          normal(1, 4096, 40, 64), log_decay(1, 4096, 40, 64),
          normal(40, 64)], 3e-2, rec),
        ("mamba2 zamba2 1x4096x80 P64 N64", ops.mamba2, ref.mamba2_ref,
         [normal(1, 4096, 80, 64), log_decay(1, 4096, 80, scale=0.3),
          normal(1, 4096, 80, 64, scale=0.125),
          normal(1, 4096, 80, 64, scale=0.125)], 3e-2, rec),
    ], jnp


def run_kernels(seed: int) -> None:
    import jax

    cases, jnp = kernel_cases()
    key = jax.random.PRNGKey(seed)
    for name, op, oracle, makers, tol, why in cases:
        args = []
        for kind, shape, scale in makers:
            key, sub = jax.random.split(key)
            z = jax.random.normal(sub, shape, jnp.float32) * scale
            if kind == "log_decay":
                z = -jnp.exp(z) if len(shape) == 4 else -jnp.abs(z)
            args.append(z.astype(jnp.bfloat16))
        t0 = time.perf_counter()
        got = jax.block_until_ready(jax.jit(functools.partial(
            op, implementation="pallas"))(*args))
        dt = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            want = jax.jit(oracle)(*args)
        got = got if isinstance(got, tuple) else (got,)
        want = want if isinstance(want, tuple) else (want,)
        errs = [_rel_err(g, w) for g, w in zip(got, want)]
        verdict = "OK" if max(errs) <= tol else "FAIL"
        print(f"kernel {name}: max|kernel-oracle|/max|oracle| = "
              f"{', '.join(f'{e:.3e}' for e in errs)} (tol {tol:g}: {why}); "
              f"first call incl. compile {dt:.2f}s {verdict}", flush=True)
        if verdict != "OK":
            _fail(f"kernel {name} disagrees with its oracle")


# ---------------------------------------------------------------------------
# phase 2: paged serving against forward()
# ---------------------------------------------------------------------------

def run_serving(arch: str, seed: int, mesh=None) -> None:
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.distributed.sharding import tp_size
    from repro.launch.serve import PagedServeEngine, init_params, \
        make_requests
    from repro.models import family_module

    cfg = get_config(arch)
    if cfg.n_experts:
        # The capacity dispatch drops tokens over capacity, and which ones
        # depends on how many tokens share a call: a 128-token prefill chunk
        # and a 1056-token forward() would route differently by design.  At
        # n_experts / top_k every expert can take every token of a call, so
        # nothing drops (as in the published, dropless model) and both paths
        # compute the same function.
        cfg = dataclasses.replace(cfg,
                                  capacity_factor=cfg.n_experts / cfg.top_k)
    tp = tp_size(mesh) if mesh is not None else 1
    mod = family_module(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        init_params(cfg, jax.random.PRNGKey(seed), tp=tp, mesh=mesh))
    print(f"serve {arch}: params made in {time.perf_counter() - t0:.1f}s "
          f"({sum(x.size for x in jax.tree_util.tree_leaves(params)) / 1e9:.2f}"
          f"B values, tp={tp})", flush=True)

    eng = PagedServeEngine(cfg, params, slots=SLOTS, max_seq=MAX_SEQ,
                           page_size=PAGE, prefill_chunk=CHUNK, mesh=mesh)
    reqs = make_requests(cfg, N_REQUESTS, MAX_NEW, seed=seed,
                         lengths=PROMPT_LENGTHS)
    target = reqs[0]
    captured: dict[int, object] = {}
    batch_step = eng._decode_batch

    def recording_step(params, cache, toks, pos, row_map):
        # the engine's own batched decode program; keep the target's row
        logits, cache = batch_step(params, cache, toks, pos, row_map)
        for s, r in eng.scheduler.active.items():
            if r is target and s not in eng._prefills:
                captured[int(eng.pos[s])] = logits[s, -1]
        return logits, cache

    eng._decode_batch = recording_step
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    done = eng.run()
    dt = time.perf_counter() - t0
    print(f"serve {arch}: {len(done)} requests, prompts "
          f"{sorted(len(r.prompt) for r in done)}, {eng.generated} tokens in "
          f"{eng.decode_steps} decode steps, {dt:.1f}s incl. compiles",
          flush=True)
    bad = [(r.rid, r.status) for r in done if r.status != "OK"
           or len(r.out) != MAX_NEW]
    if bad:
        _fail(f"requests not served in full: {bad}")

    n = len(target.prompt)
    seq = np.concatenate([target.prompt, np.asarray(target.out, np.int32)])
    fwd = jax.jit(lambda p, t: mod.forward(p, cfg, {"tokens": t}, tp=tp,
                                           impl="xla"))
    ref = np.asarray(fwd(params, jax.numpy.asarray(seq[None]))[0],
                     np.float32)[:, :cfg.vocab]
    positions = sorted(captured)
    if positions != list(range(n, n + MAX_NEW - 1)):
        _fail(f"captured decode positions {positions[:3]}... for a "
              f"{n}-token prompt")
    got = np.stack([np.asarray(captured[p], np.float32)[:cfg.vocab]
                    for p in positions])
    want = ref[positions]
    diff = np.abs(got - want)
    tol = LOGIT_TOL_STD * float(want.std())
    top2 = np.sort(want, axis=-1)[:, -2:]
    gap = top2[:, 1] - top2[:, 0]
    decided = gap > tol
    engine_tok = np.asarray(target.out[1:])
    agree = engine_tok[decided] == want.argmax(-1)[decided]
    print(f"serve {arch}: request {target.rid} ({n}-token prompt), "
          f"{len(positions)} decode positions: max|engine-forward| logit = "
          f"{diff.max():.4f}, mean {diff.mean():.5f}, reference logit std "
          f"{want.std():.3f} (tol {LOGIT_TOL_STD} std = {tol:.4f}); "
          f"tokens = forward argmax "
          f"at {int(agree.sum())}/{int(decided.sum())} positions whose "
          f"top-2 gap exceeds the tol", flush=True)
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"serve {arch}: peak device memory "
          f"{'not reported' if peak is None else f'{peak / 2**30:.2f} GiB'} "
          f"(device 0)", flush=True)
    if diff.max() > tol:
        _fail(f"engine logits differ from forward by {diff.max():.4f}")
    if not agree.all():
        _fail("engine tokens differ from forward argmax where decided")


# ---------------------------------------------------------------------------
# phase 3: measured co-design
# ---------------------------------------------------------------------------

def run_codesign(seed: int, db_path: Path) -> None:
    from repro.core import workloads as W
    from repro.core.codesign import codesign
    from repro.tuner.db import TuningDB
    from repro.tuner.measure import MeasureOptions

    # granite-moe-3b-a800m at 512 tokens: the q and o projections
    # (1536 -> 1536), and the kv projection and an expert's up-projection
    # (1536 -> 512)
    wl = [W.gemm(512, 1536, 1536, name="granite_q_o"),
          W.gemm(512, 512, 1536, name="granite_kv_up")]
    t0 = time.perf_counter()
    rep = codesign(wl, intrinsics=["GEMM"], target="tpu", n_trials=8,
                   n_init=4, seed=seed, measure=True, measure_backend="pallas",
                   measure_top_k=4,
                   measure_opts=MeasureOptions(backend="pallas",
                                               dtype="bfloat16"),
                   db_path=db_path, app="granite-moe-3b-a800m")
    dt = time.perf_counter() - t0
    s = (rep.measured or {}).get("GEMM", {})
    db = TuningDB.load(db_path)
    timed = {w.name: db.best_record(
        "gemm", tuple(w.extents[i] for i in "ijk"), "bfloat16", "pallas")
        for w in wl}
    print(f"codesign: {dt:.1f}s, solution "
          f"{rep.solution.describe() if rep.solution else None}; summary {s}",
          flush=True)
    for f in db.failures:
        print(f"codesign: candidate failed on {f.get('workload')}: "
              f"{f.get('error_type')}: {str(f.get('error'))[:300]}",
              flush=True)
    for name, r in timed.items():
        print(f"codesign: {name}: "
              + ("no timed candidate" if r is None else
                 f"best timed blocks {r.blocks}: {r.measured_s * 1e6:.1f} us "
                 f"measured, {r.predicted_s * 1e6:.1f} us predicted"),
              flush=True)
    if rep.solution is None or s.get("best_has_fallbacks", True):
        _fail("codesign committed no fully measured solution")
    if any(r is None or not math.isfinite(r.measured_s)
           for r in timed.values()):
        _fail("a workload has no timed candidate")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only qwen3-8b paged serving on a data=1, "
                         "model=4 mesh, against forward() on that mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: the repository's src/repro is missing next to "
              f"{Path(__file__).name}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    from repro.kernels import ops
    from repro.launch import compile_cache

    cache_dir = compile_cache.enable()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({len(devices)} device(s))", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}; compile cache {cache_dir}", flush=True)

    if args.chips == 4:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1, 4), ("data", "model"), devices=devices[:4])
        phases = [("serving qwen3-8b data=1,model=4",
                   lambda: run_serving("qwen3-8b", args.seed, mesh))]
    else:
        phases = [("kernels", lambda: run_kernels(args.seed)),
                  ("serving granite-moe-3b-a800m",
                   lambda: run_serving("granite-moe-3b-a800m", args.seed)),
                  ("codesign", lambda: run_codesign(args.seed, db_path))]

    failed = []
    with tempfile.TemporaryDirectory() as tmp:
        # a tuning database of this run's own: dispatch never reads blocks
        # from a leftover artifacts/tuning_db.json
        db_path = Path(tmp) / "tuning_db.json"
        ops.set_tuning_db(db_path)
        for name, fn in phases:
            t0 = time.perf_counter()
            try:
                fn()
            except Exception:   # report every phase, then fail the run
                traceback.print_exc()
                failed.append(name)
            print(f"phase {name}: {'FAIL' if name in failed else 'ok'} in "
                  f"{time.perf_counter() - t0:.1f}s", flush=True)
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
