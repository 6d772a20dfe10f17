"""The whole step's share of the chips' peak, from the trace: model FLOPs
of every decode and prefill call whose execution the trace holds whole
(routed experts only, the head only where its logits are used, as the
family counts them), over the seconds each device was busy from the first
of those executions to the end of the last, summed over the devices, times
one chip's peak FLOP/s.  Busy time counts every program and op a device ran
there, the step's own and the small ones around it, so work that a later
change moves off the step's program still counts."""
from bench import devtrace


def read(r):
    pairs = r.device_calls("decode") + r.device_calls("prefill")
    if not pairs or r.peaks is None:
        return None
    flops = sum(r.family.decode_step(r.cfg, c.contexts)[0]
                if c.kind == "decode" else r.family.prefill_chunk(r.cfg,
                                                                  *c.chunk)
                for c, _ in pairs)
    span = devtrace.Interval("steps", min(d.start for _, d in pairs),
                             max(d.end for _, d in pairs))
    busy = sum(devtrace.seconds(devtrace.busy(r.trace, span, d))
               for d in r.trace.devices)
    return 100.0 * flops / (busy * r.peaks["flops_bf16"])
