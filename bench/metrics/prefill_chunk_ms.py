"""Device time per prefill-chunk call (the jitted step on a request's
private cache, through ``PagedServeEngine._decode``), from the trace."""


def read(r):
    calls = r.device_calls("prefill")
    return 1e3 * sum(d.dur for _, d in calls) / len(calls) if calls else None
