"""Readings that set a cell's limit: the program's on many seeds, and the
control's on the same prompts and tokens.  Run on the chip, in one process.

    python3 bench/control.py --workload <cell> --seconds 8 --seeds 1 2 3 ...

For each seed: one run of the cell as ``run.py`` makes it (a short window at
the cell's own load, drained, its sample checked against the reference),
then the family's reference computed in float8 (``reference.mm``) in the
program's place, on the first chip, read at every position of the same
prompts and served tokens.
Prints one JSON line per seed: the widest and the mean gap of the served
tokens (the lower readings' material) and of the control's first choices
(the upper readings').  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]


def main(argv=None) -> int:
    import jax

    from bench import check, spec, weights
    from bench.run import NoChip, cell_mesh, run_cell

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    cfg = spec.model_config(cell.config, cell.model_axis)
    dims = cell.family.Dims.of(cell.config)
    for seed in args.seeds:
        kept = {}
        try:
            result = run_cell(args.workload, seed, args.seconds, False,
                              break_path=lambda run: kept.update(run=run))
        except NoChip as e:
            print(f"no chip: {e}", file=sys.stderr)
            return 3
        run = kept["run"]
        w = jax.block_until_ready(weights.make(
            cell.family, cfg, seed,
            cell_mesh(cell, jax.devices()[:cell.chips])))
        idxs = check.sample(run, seed, cell.correct["sample_requests"])
        line = {"seed": seed, "correct": result["correct"],
                "served": {k: c["value"]
                           for k, c in result["checks"].items()},
                "attempted": result["attempted"], "failed": result["failed"]}
        line["fp8"] = check.control_gap(
            cell.family, w, dims, run, idxs, pad_to=cell.engine["max_seq"],
            mode="fp8")[1]
        print(json.dumps(line), flush=True)
        del w, run, kept
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
