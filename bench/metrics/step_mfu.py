"""The whole step's share of the chip's peak, from the trace: model FLOPs of
every decode and prefill call whose execution the trace holds whole (routed
experts only, the head only where its logits are used), over the seconds in
which the device was busy from the first of those executions to the end of
the last, times the peak FLOP/s.  Busy time counts every program and op the
device ran there, the step's own and the small ones around it, so work that
a later change moves off the step's program still counts."""
from bench import counters, devtrace


def read(r):
    pairs = r.device_calls("decode") + r.device_calls("prefill")
    if not pairs or r.peaks is None:
        return None
    flops = sum(counters.decode_step(r.cfg, c.contexts)[0]
                if c.kind == "decode" else counters.prefill_chunk(r.cfg,
                                                                  *c.chunk)
                for c, _ in pairs)
    span = devtrace.Interval("steps", min(d.start for _, d in pairs),
                             max(d.end for _, d in pairs))
    # seconds in which any chip was busy, times the chips' peak
    busy = sum(b - a for a, b in devtrace.busy(r.trace, span))
    return 100.0 * flops / (busy * r.trace.n_devices * r.peaks["flops_bf16"])
