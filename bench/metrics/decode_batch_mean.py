"""Tokens the engine emitted per batched decode step over the window, from
its own counters: the change in ``generated`` over the change in
``decode_steps``.  ``generated`` also counts each prompt's first token,
which a prefill emits: a few in a window of hundreds of steps."""


def read(r):
    gen, steps = (r.run.counters.get(k, (0, 0))
                  for k in ("generated", "decode_steps"))
    return (gen[1] - gen[0]) / (steps[1] - steps[0]) \
        if steps[1] > steps[0] else None
