"""Find the knee of an open-loop cell: the highest offered rate that the
engine serves without a growing backlog.  Run on the chip, once, when the
cell is defined; the cell's file then fixes 0.8 times that rate.

    python3 bench/sweep.py --workload <cell> --rates 1 1.5 2 2.5 --seconds 30

One process: the weights and compiled programs are made once, then each rate
gets a fresh engine and a window of its own.  A rate is sustained when the
mean queue wait (admission minus due time) of the requests due in the
window's last quarter is at most twice that of its second quarter plus
0.25 s: a backlog that grows shows as a queue wait that grows.  Prints one
JSON line per rate.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]


def sustained(served, start: float, seconds: float) -> tuple[bool, float,
                                                              float]:
    """(sustained, mean queue wait of requests due in the window's second
    quarter, of those due in its last quarter).  Queue wait is admission to
    a slot minus the due time: it stays flat while the engine keeps up and
    grows with the backlog once it does not."""
    def mean_wait(lo, hi):
        w = [s.admitted - s.due for s in served
             if s.admitted is not None
             and start + lo * seconds <= s.due < start + hi * seconds]
        return sum(w) / len(w) if w else float("inf")
    q2, q4 = mean_wait(0.25, 0.5), mean_wait(0.75, 1.0)
    return q4 <= 2 * q2 + 0.25, q2, q4


def main(argv=None) -> int:
    import jax

    from bench import serving, spec, stats, weights
    from bench.run import cell_mesh, enable_compile_cache

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    if jax.devices()[0].platform != "tpu":
        print("no chip: the sweep measures on a TPU", file=sys.stderr)
        return 3
    enable_compile_cache()
    cell = spec.load_cell(args.workload)
    cfg = spec.model_config(cell.config, cell.model_axis)
    mesh = cell_mesh(cell, jax.devices()[:cell.chips])
    w = jax.block_until_ready(weights.make(cell.family, cfg, args.seed,
                                           mesh))
    for rate in args.rates:
        c = dataclasses.replace(cell, load={"rate_per_s": rate})
        run = serving.Run(c, cfg, w, seed=args.seed, seconds=args.seconds,
                          annotate=False, mesh=mesh)
        run.warm_up()
        run.window_open()
        run.drain()
        served = list(run.served.values())
        ok, q2, q4 = sustained(served, run.window.start, args.seconds)
        ttft = stats.ttfts(served, run.window)
        print(json.dumps({
            "rate_per_s": rate, "sustained": ok,
            "queue_wait_q2_s": q2, "queue_wait_q4_s": q4,
            "output_tok_s": stats.output_tokens(served, run.window)
            / run.window.seconds,
            "ttft_p50_s": stats.percentile(ttft, 50),
            "ttft_p95_s": stats.percentile(ttft, 95),
            "unfinished": run.failed()}), flush=True)
        run.free()
        del run
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
