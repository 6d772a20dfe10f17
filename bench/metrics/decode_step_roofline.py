"""Share of the roofline reached by the batched decode program: the least
time each traced call could take on the chip (its operations over peak
FLOP/s or its bytes over peak bandwidth, whichever is larger; bytes are
every weight the batch uses once plus every live KV row once, as
``counters.decode_step`` counts them), summed over the calls, over their
device time."""
from bench import counters


def read(r):
    calls = r.device_calls("decode")
    if not calls or r.peaks is None:
        return None
    least = 0.0
    for c, _ in calls:
        flops, nbytes = counters.decode_step(r.cfg, c.contexts)
        least += max(flops / r.peaks["flops_bf16"],
                     nbytes / r.peaks["hbm_bytes_per_s"])
    return 100.0 * least / sum(d.dur for _, d in calls)
