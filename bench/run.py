"""The benchmark's one command: one run of one cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the weights on the device from the seed (on a cell of
several chips, sharded over the cell's mesh as the program shards them),
loads or compiles every program the cell's traffic uses and warms each
shape; the window then
offers the cell's load to ``PagedServeEngine`` for ``--seconds``; the run
drains what it owes, reads its metrics, frees the engine and checks a sample
of the served tokens against the plain reference (``check.py``).  With
``--trace 1`` the window's last few seconds are traced by the profiler and
the per-layer metrics are reported; with ``--trace 0`` the end-to-end ones.  The last line of standard output is one JSON object; the
numbers compared for ``correct`` are also the last lines of standard error.
Without a TPU, or with fewer chips than the cell asks for, the run exits 3
and prints no result; a traced run that the profiler slowed by more than
``TRACE_SLOWDOWN_LIMIT_PCT`` exits 4 and prints none.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse   # noqa: E402
import gc   # noqa: E402
import json   # noqa: E402
import math   # noqa: E402
import statistics   # noqa: E402
import sys   # noqa: E402
from pathlib import Path   # noqa: E402

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]

OUT = REPO / ".bench_out"
# the traced run traces the window's last TRACE_SPAN_S seconds, so that the
# profiler's stop (seconds of host work) falls after the window's close
TRACE_SPAN_S = 4.0
# a traced run whose decode-only ticks are slower than the rest of its
# window's by more than this share is refused: its per-layer readings would
# not describe the system that is timed
TRACE_SLOWDOWN_LIMIT_PCT = 5.0


class NoChip(RuntimeError):
    pass


class TraceTooCostly(RuntimeError):
    pass


class Tracer:
    """Starts the profiler ``lead`` seconds into the window, between engine
    steps; ``finish`` stops it once the window has closed.  ``warm`` takes
    a throwaway trace during set-up, so that the profiler's own start-up is
    not paid inside the window."""

    def __init__(self, directory: Path, lead: float):
        self.directory, self.lead = str(directory), lead
        self.h0 = self.h1 = None
        self._ann = None

    def warm(self) -> None:
        import jax.numpy as jnp
        from bench import devtrace

        devtrace.start(self.directory)
        jnp.zeros(8).block_until_ready()
        devtrace.stop()

    def hook(self, now: float, start: float) -> None:
        from bench import devtrace
        from bench.serving import clock

        if self.h0 is None and now >= start + self.lead:
            import jax
            devtrace.start(self.directory)
            self._ann = jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN)
            self.h0 = clock()
            self._ann.__enter__()
            print(f"profiler started in {self.h0 - now:.3f} s",
                  file=sys.stderr)

    def finish(self) -> None:
        from bench import devtrace
        from bench.serving import clock

        if self.h0 is None:
            return
        self._ann.__exit__(None, None, None)
        self.h1 = clock()
        devtrace.stop()
        print(f"profiler stopped in {clock() - self.h1:.3f} s",
              file=sys.stderr)


def _peaks(kind: str) -> dict:
    with open(REPO / "bench" / "peaks.json") as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def enable_compile_cache() -> str:
    """The program's persistent cache (``$JAX_COMPILATION_CACHE_DIR``, else
    ``<checkout>/.jax_cache``), holding every program however fast it
    compiled, so that only a checkout's first run compiles."""
    import jax
    from repro.launch import compile_cache

    where = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def cell_mesh(cell, devices):
    """The cell's ``(data, model)`` mesh over ``devices``; None on one
    chip."""
    if cell.mesh is None:
        return None
    from bench.spec import MESH_AXES
    from repro.launch.mesh import make_mesh

    return make_mesh(tuple(cell.mesh[a] for a in MESH_AXES), MESH_AXES,
                     devices=devices)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, root: Path = REPO,
             break_path=None) -> dict:
    """One run; returns the result line.  ``require_chip=False`` and
    ``break_path(run)`` (called once the engine exists) are for the tests,
    which drive a run on the CPU with the timed path broken."""
    import jax

    from bench import check, devtrace, readings, serving, spec, weights

    cell = spec.load_cell(name, root)
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell.chips):
        raise NoChip(f"cell {name} needs {cell.chips} TPU chip(s); JAX "
                     f"finds {len(devices)} {devices[0].platform} device(s)")
    devices = devices[:cell.chips]
    peaks = _peaks(devices[0].device_kind) if require_chip else None
    if require_chip:
        enable_compile_cache()

    cfg = spec.model_config(cell.config, cell.model_axis, root)
    mesh = cell_mesh(cell, devices)
    w = jax.block_until_ready(weights.make(cell.family, cfg, seed, mesh))
    run = serving.Run(cell, cfg, w, seed=seed, seconds=seconds,
                      annotate=trace, mesh=mesh)
    if break_path is not None:
        break_path(run)
    run.warm_up()
    tracer = None
    if trace:
        tracer = Tracer(OUT / "trace" / name,
                        seconds - min(TRACE_SPAN_S, 0.3 * seconds))
        tracer.warm()
    run.window_open(tracer.hook if tracer else None)
    if tracer is not None:
        tracer.finish()
    setup_s = run.window.start - T_PROCESS
    run.drain()
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)

    reading = readings.Reading(run, cfg, peaks, setup_s,
                               family=cell.family)
    cost = None
    if tracer is not None and tracer.h0 is not None:
        reading.trace = devtrace.load(tracer.directory)
        reading.traced = serving.Window(tracer.h0, tracer.h1)
        cost = readings.trace_cost(run, reading.traced)
        print("trace cost: " + json.dumps(cost), file=sys.stderr)
        if cost.get("slowdown_pct", 0.0) > TRACE_SLOWDOWN_LIMIT_PCT:
            raise TraceTooCostly(
                f"decode-only ticks ran {cost['slowdown_pct']:.2f} % slower "
                f"while traced (limit {TRACE_SLOWDOWN_LIMIT_PCT} %)")
    bench = spec.benchmark(root)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in readings.metric_names(bench, name, kind):
        value = readings.reader(m["name"])(reading)
        # an infinite tail (a request never answered) fails `correct`
        # through `unanswered`; JSON has no infinity
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    print(f"compiles in the window: {run.compiles.count}", flush=True)
    late = [s.sent - s.due for s in run.counted() if s.due is not None]
    if late:
        print(f"generator lateness: p50 {statistics.median(late):.6f} s, "
              f"max {max(late):.6f} s over {len(late)} requests", flush=True)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem_peak}
    result = {"attempted": len(run.counted()), "failed": run.failed(),
              "metrics": metrics, "device": device}
    if cost is not None:
        result["trace_cost"] = cost
    if reading.trace is not None:
        device["busy_s"] = devtrace.busy_seconds(reading.trace)
        device["window_s"] = reading.trace.window.dur
        result["breakdown"] = {"device_ops": devtrace.top_ops(reading.trace),
                               "idle_gaps": devtrace.top_gaps(reading.trace)}

    # correctness, once the engine's state is freed
    idxs = check.sample(run, seed, cell.correct["sample_requests"])
    unanswered = run.failed()
    run.free()
    gc.collect()
    t_ref = time.perf_counter()
    got = check.served_gap(cell.family, w, cell.family.Dims.of(cell.config),
                           run, idxs, pad_to=cell.engine["max_seq"])
    compared = got["tokens_compared"]
    slots = sorted({run.served[i].slot for i in idxs}, key=str)
    print(f"reference: {len(idxs)} requests from slots {slots}, {compared} "
          f"tokens, {time.perf_counter() - t_ref:.1f} s", file=sys.stderr)
    checks = {name: {"value": got.get(name), "limit": cell.correct[name]}
              for name in ("max_logit_gap", "mean_logit_gap")
              if name in cell.correct}
    checks["unanswered"] = {"value": unanswered, "limit": 0}
    checks["tokens_compared"] = {"value": compared, "limit": 1,
                                 "at_least": True}
    result["correct"] = compared > 0 and all(
        c["value"] >= c["limit"] if c.get("at_least")
        else c["value"] <= c["limit"] for c in checks.values())
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        print(f"no chip: {e}", file=sys.stderr)
        return 3
    except TraceTooCostly as e:
        print(f"trace refused: {e}", file=sys.stderr)
        return 4
    ordered = {k: result[k] for k in ("correct", "attempted", "failed",
                                      "metrics", "device")}
    for k in ("breakdown", "trace_cost"):
        if k in result:
            ordered[k] = result[k]
    ordered["checks"] = result["checks"]
    for k, c in result["checks"].items():
        rel = ">=" if c.get("at_least") else "<="
        print(f"check {k} = {c['value']} (limit {rel} {c['limit']})",
              file=sys.stderr)
    print(json.dumps(ordered, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
