"""Device time per call of the batched decode program, from the trace: each
call the harness made through ``PagedServeEngine._decode_batch``, paired
with its execution of the step's program."""


def read(r):
    calls = r.device_calls("decode")
    return 1e3 * sum(d.dur for _, d in calls) / len(calls) if calls else None
