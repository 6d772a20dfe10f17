"""Tensor-parallel serving gate (DESIGN.md §15).

Three scenarios, all gated (exit 1 on miss):

  * ``model=1``: the paged engine hosted on a (data=1, model=1) mesh must
    produce BIT-IDENTICAL per-request outputs to the plain
    ``PagedServeEngine`` — a trivial mesh adds sharding machinery but no
    collectives, so any drift is a bug in the mesh plumbing, not numerics.
  * ``8-way``: a subprocess widened to 8 host devices
    (``--xla_force_host_platform_device_count``) decodes the same traffic
    on a (data=1, model=8) mesh and on a single device with the SAME tp=8
    padded params; greedy tokens must match token-for-token (sharded
    reductions may reassociate ulps; argmax token identity is the
    contract).
  * ``codesign``: with the interconnect term in the cost model, a seeded
    codesign run over (chip config × TP degree) must commit a *different*,
    TP-aware solution (hw.tp > 1, lower modeled latency) than the TP-blind
    run on the same workloads.

Tokens/s for every serving run is published into
``artifacts/bench_results.json``.

    PYTHONPATH=src python -m benchmarks.bench_serve_tp
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src"
sys.path.insert(0, str(SRC))

SLOTS = 4
MAX_SEQ = 48
N_REQUESTS = 8
MAX_NEW = 8
PAGE_SIZE = 8
PREFILL_CHUNK = 16

LAST_METRICS: dict = {}


def _serve(cfg, params, *, tp=1, mesh=None):
    from repro.launch.serve import make_requests, serve_requests

    reqs = make_requests(cfg, N_REQUESTS, MAX_NEW, seed=0)
    t0 = time.perf_counter()
    done, stats = serve_requests(
        cfg, params, reqs, slots=SLOTS, max_seq=MAX_SEQ, tp=tp, mesh=mesh,
        paged=True, page_size=PAGE_SIZE, prefill_chunk=PREFILL_CHUNK)
    dt = time.perf_counter() - t0
    done = sorted(done, key=lambda r: r.rid)
    return [r.out for r in done], stats["generated"] / dt


def run_model1() -> dict:
    """Trivial mesh vs no mesh: bit-identical outputs on one device."""
    import jax

    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.models import family_module, reduced

    cfg = reduced(get_config("qwen3-8b"))
    params = family_module(cfg).init(cfg, jax.random.PRNGKey(0), tp=1)
    mesh = make_host_mesh(tp=1)
    for _ in range(2):                       # second run is the warm timing
        outs_plain, tok_s_plain = _serve(cfg, params)
        outs_mesh, tok_s_mesh = _serve(cfg, params, mesh=mesh)
    return {
        "tok_s_plain": round(tok_s_plain, 1),
        "tok_s_mesh": round(tok_s_mesh, 1),
        "outputs_identical": outs_plain == outs_mesh,
    }


_TP8 = textwrap.dedent("""
    import dataclasses, json, os, sys, time
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    sys.path.insert(0, "__SRC__")
    import jax
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import make_requests, serve_requests
    from repro.models import family_module, reduced

    # f32 so token identity is a meaningful gate: sharded partial sums
    # round at shard boundaries, and bf16's 2^-8 steps are the same order
    # as this random-init model's top-2 logit gaps — f32 leaves ~60x
    # margin between reassociation drift and the closest gap
    cfg = dataclasses.replace(reduced(get_config("qwen3-8b")),
                              dtype="float32")
    params = family_module(cfg).init(cfg, jax.random.PRNGKey(0), tp=8)
    mesh = make_host_mesh(tp=8)
    assert dict(mesh.shape) == {"data": 1, "model": 8}

    def serve(mesh_arg):
        reqs = make_requests(cfg, __N__, __MAX_NEW__, seed=0)
        t0 = time.perf_counter()
        done, stats = serve_requests(
            cfg, params, reqs, slots=__SLOTS__, max_seq=__MAX_SEQ__,
            tp=8, mesh=mesh_arg, paged=True, page_size=__PAGE_SIZE__,
            prefill_chunk=__CHUNK__)
        dt = time.perf_counter() - t0
        done = sorted(done, key=lambda r: r.rid)
        return [r.out for r in done], stats["generated"] / dt

    for _ in range(2):                      # second run is the warm timing
        outs_ref, tok_s_ref = serve(None)   # single device, same tp=8 params
        outs_tp, tok_s_tp = serve(mesh)
    print(json.dumps({"outputs_identical": outs_ref == outs_tp,
                      "tok_s_single": round(tok_s_ref, 1),
                      "tok_s_tp8": round(tok_s_tp, 1)}))
""")


def run_tp8() -> dict:
    """8-way mesh decode vs single-device, same tp=8 params, in a widened
    subprocess (the host device count is fixed at jax import)."""
    script = (_TP8.replace("__SRC__", str(SRC))
              .replace("__N__", str(N_REQUESTS))
              .replace("__MAX_NEW__", str(MAX_NEW))
              .replace("__SLOTS__", str(SLOTS))
              .replace("__MAX_SEQ__", str(MAX_SEQ))
              .replace("__PAGE_SIZE__", str(PAGE_SIZE))
              .replace("__CHUNK__", str(PREFILL_CHUNK)))
    # 8 virtual host devices is a CPU check by design; the child stays off
    # any accelerator, which the parent (a JAX user already) holds
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    if out.returncode != 0:
        raise SystemExit(f"tp8 subprocess failed:\n{out.stdout}\n{out.stderr}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def run_codesign() -> dict:
    """Seeded (chip × TP) search vs the TP-blind search: the interconnect
    term must change the committed solution."""
    from repro.core import workloads as W
    from repro.core.codesign import codesign
    from repro.core.hw_space import PARALLELISM_AXES

    wl = W.table1_gemm()[:2]
    kw = dict(intrinsics=["GEMM"], n_trials=8, n_init=4, seed=0, q=2)
    blind = codesign(wl, **kw).solution
    aware = codesign(wl, space_axes=PARALLELISM_AXES, **kw).solution
    return {
        "hw_blind": list(blind.hw.encode()),
        "hw_aware": list(aware.hw.encode()),
        "tp_blind": blind.hw.tp,
        "tp_aware": aware.hw.tp,
        "latency_blind_s": blind.latency_s,
        "latency_aware_s": aware.latency_s,
        "solutions_differ": blind.hw.encode() != aware.hw.encode(),
    }


def main() -> None:
    global LAST_METRICS
    from benchmarks._results import publish

    m1 = run_model1()
    m8 = run_tp8()
    mc = run_codesign()
    ok = bool(m1["outputs_identical"] and m8["outputs_identical"]
              and mc["solutions_differ"] and mc["tp_aware"] > 1
              and mc["latency_aware_s"] < mc["latency_blind_s"])
    m = {"model1": m1, "tp8": m8, "codesign": mc, "pass": ok}
    LAST_METRICS = m

    print("bench,case,detail")
    print(f"bench_serve_tp,model1_bit_exact,"
          f"identical={m1['outputs_identical']},"
          f"tok_s={m1['tok_s_mesh']}_vs_{m1['tok_s_plain']}")
    print(f"bench_serve_tp,tp8_token_exact,"
          f"identical={m8['outputs_identical']},"
          f"tok_s={m8['tok_s_tp8']}_vs_{m8['tok_s_single']}")
    print(f"bench_serve_tp,codesign_tp_aware,"
          f"tp={mc['tp_aware']}_vs_{mc['tp_blind']},"
          f"latency={mc['latency_aware_s']:.3g}_vs_"
          f"{mc['latency_blind_s']:.3g}")
    publish("bench_serve_tp", m, failed=not ok)
    if not ok:
        raise SystemExit(
            f"bench_serve_tp gate missed: model1_identical="
            f"{m1['outputs_identical']} tp8_identical="
            f"{m8['outputs_identical']} codesign_differ="
            f"{mc['solutions_differ']} tp_aware={mc['tp_aware']}")


if __name__ == "__main__":
    main()
