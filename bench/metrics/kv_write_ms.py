"""Device ms per traced call of the batched decode program of the ops whose
innermost named scope is ``kv_write``: the paged scatter of each layer's
new K/V rows into the pool (``layers.attention``'s ``put``)."""
from bench import progtrace


def read(r):
    return progtrace.decode_scope_ms(r, "kv_write")
