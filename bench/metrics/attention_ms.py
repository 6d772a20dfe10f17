"""Device ms per traced call of the batched decode program of the ops whose
innermost named scope is ``attention``: the q/k/v/o projections, the page
gather and the f32 softmax (``kv_write`` counted apart)."""
from bench import progtrace


def read(r):
    return progtrace.decode_scope_ms(r, "attention")
