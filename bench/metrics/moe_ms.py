"""Device ms per traced call of the batched decode program of the ops whose
innermost named scope is ``moe``: routing, the capacity dispatch and the
expert einsums (``layers.moe``)."""
from bench import progtrace


def read(r):
    return progtrace.decode_scope_ms(r, "moe")
