"""Share of the roofline reached by the batched decode program: the least
time each traced call could take on the chips that ran it (its operations
over their peak FLOP/s or its bytes over their peak bandwidth, whichever is
larger; bytes are every weight the batch uses once plus every live KV row
once, as the family's ``decode_step`` counts them), summed over the calls,
over their device time.  On n chips the peaks are n chips' peaks, so a
weight replicated on every chip counts once and shows as a lower share."""


def read(r):
    calls = r.device_calls("decode")
    if not calls or r.peaks is None:
        return None
    n = r.trace.n_devices
    least = 0.0
    for c, _ in calls:
        flops, nbytes = r.family.decode_step(r.cfg, c.contexts)
        least += max(flops / (n * r.peaks["flops_bf16"]),
                     nbytes / (n * r.peaks["hbm_bytes_per_s"]))
    return 100.0 * least / sum(d.dur for _, d in calls)
