"""Jaxpr hot-path auditor (DESIGN.md §16.3).

Traces a jitted serve/train step and statically inspects the resulting
jaxpr for the failure modes that cost real serving throughput without ever
raising an exception:

  * host syncs / device-to-host transfers inside the program — callback
    primitives (``jax.debug.print``, ``pure_callback``, ``io_callback``)
    block the dispatch pipeline every tick;
  * python-scalar / host-state captures — a python scalar closed over by a
    step function is baked into the jaxpr at trace time, so engine state
    that should flow as an argument either goes stale (cached jit) or
    forces a retrace per tick (fresh wrapper).  Statically these fold into
    literals indistinguishable from code constants, so the robust detector
    is differential: trace the program at two consecutive engine states
    (same shapes/dtypes, different values) and diff the canonicalized
    jaxprs — any difference proves the program depends on host state the
    arguments do not carry;
  * silent recompiles across ticks — drive the *actual jitted callable*
    with two same-shaped tick inputs and assert its compilation-cache size
    stops growing after the first call;
  * weak-typed inputs (python scalars passed as traced args: their dtype
    rides python promotion and splits the jit cache) and missed donations
    (an output aval that matches a large non-donated input aval means two
    live copies of a buffer the program could have reused in place).

``audit_hot_paths`` bundles the shipped serve decode / chunked-prefill /
slot-write / train-step programs for one model config — the program set
``tests/test_analysis_audit.py`` pins clean and the CLI's ``--audit``
re-checks.
"""
from __future__ import annotations

import numpy as np

from .findings import Finding, rule

R_HOST_CALLBACK = rule(
    "jaxpr/host-callback",
    "callback primitive inside a jitted hot path: every invocation "
    "synchronizes with the host")
R_STATE_TRACE = rule(
    "jaxpr/state-dependent-trace",
    "program traced at two same-shaped engine states produced different "
    "jaxprs: host state (e.g. a python scalar) is captured by closure "
    "instead of flowing as an argument — stale under a cached jit, a "
    "retrace per tick under a fresh one")
R_RECOMPILE = rule(
    "jaxpr/recompile",
    "jit compilation cache grew on a same-shaped tick: the program "
    "silently recompiles across ticks")
R_WEAK_ARG = rule(
    "jaxpr/weak-type-arg",
    "weak-typed scalar argument: the traced dtype rides python promotion "
    "and value-class changes split the jit cache")
R_SCALAR_CONST = rule(
    "jaxpr/scalar-capture",
    "weak-typed scalar constant captured from the enclosing scope")
R_BIG_CONST = rule(
    "jaxpr/large-const-capture",
    "large array captured by closure: baked into every compiled "
    "executable instead of passed as an argument")
R_MISSED_DONATION = rule(
    "jaxpr/missed-donation",
    "an output buffer matches a large non-donated input (shape+dtype): "
    "the program holds two live copies where donation would reuse one")
R_NO_INTROSPECTION = rule(
    "jaxpr/no-cache-introspection",
    "the jit callable exposes no _cache_size; recompile check skipped")

#: Primitives that synchronize with the host when hit inside a program.
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "host_callback_call", "outside_call", "debug_print",
})

_BIG_CONST_BYTES = 1 << 20      # 1 MiB: above this, closure capture is
_DONATION_BYTES = 1 << 16       # worth flagging; below, it's a lookup table


def _iter_eqns(jaxpr):
    """Yield every eqn in a jaxpr, recursing into sub-jaxpr params
    (pjit/scan/while/cond bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", None)
            if sub is not None and hasattr(sub, "eqns"):
                yield from _iter_eqns(sub)
            elif hasattr(v, "eqns"):
                yield from _iter_eqns(v)
            elif isinstance(v, (list, tuple)):
                for x in v:
                    sub = getattr(x, "jaxpr", None)
                    if sub is not None and hasattr(sub, "eqns"):
                        yield from _iter_eqns(sub)
                    elif hasattr(x, "eqns"):
                        yield from _iter_eqns(x)


def _aval_bytes(aval) -> int:
    try:
        return int(np.prod(aval.shape)) * aval.dtype.itemsize
    except Exception:
        return 0


def _leaves(args):
    import jax
    return jax.tree_util.tree_leaves(args)


def audit_program(fn, *example_args, donate_argnums: tuple[int, ...] = (),
                  site: str = "program") -> list[Finding]:
    """Trace ``fn`` on example inputs and statically audit the jaxpr."""
    import jax

    closed = jax.make_jaxpr(fn)(*example_args)
    out: list[Finding] = []

    for eqn in _iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if name in CALLBACK_PRIMS:
            cb = eqn.params.get("callback", "")
            out.append(Finding("error", R_HOST_CALLBACK, site,
                               f"primitive {name!r} ({cb}) synchronizes "
                               f"with the host every invocation"))
    # a jitted ``fn`` traces to one call (``jit``; ``pjit`` before jax 0.7)
    # whose params say which arguments it donates
    donated_flat: list[bool] = []
    eqns = closed.jaxpr.eqns
    if len(eqns) == 1 and eqns[0].primitive.name in ("jit", "pjit"):
        donated_flat = list(eqns[0].params.get("donated_invars", ()))

    for var, const in zip(closed.jaxpr.constvars, closed.consts):
        aval = var.aval
        nb = _aval_bytes(aval)
        if getattr(aval, "weak_type", False) and aval.ndim == 0:
            out.append(Finding("error", R_SCALAR_CONST, site,
                               f"weak {aval.dtype} scalar captured by "
                               f"closure (value {const!r})"))
        elif nb > _BIG_CONST_BYTES:
            out.append(Finding("warning", R_BIG_CONST, site,
                               f"{aval.dtype}{list(aval.shape)} constant "
                               f"({nb} bytes) captured by closure"))

    in_avals = list(closed.in_avals)
    for i, aval in enumerate(in_avals):
        if getattr(aval, "weak_type", False):
            out.append(Finding("warning", R_WEAK_ARG, site,
                               f"arg {i} is weak-typed {aval.dtype}: pass "
                               f"a committed array/np scalar instead"))

    # -- missed donation: output avals that match big non-donated inputs -----
    if not donated_flat:
        flat_args = _leaves(example_args)
        donated_leaves: set[int] = set()
        pos = 0
        for i, a in enumerate(example_args):
            n = len(_leaves(a))
            if i in donate_argnums:
                donated_leaves.update(range(pos, pos + n))
            pos += n
        donated_flat = [j in donated_leaves for j in range(len(flat_args))]
    avail: dict[tuple, int] = {}
    for j, aval in enumerate(in_avals):
        if j < len(donated_flat) and donated_flat[j]:
            continue
        nb = _aval_bytes(aval)
        if nb >= _DONATION_BYTES:
            key = (tuple(aval.shape), str(aval.dtype))
            avail[key] = avail.get(key, 0) + 1
    missed = missed_bytes = 0
    for aval in closed.out_avals:
        key = (tuple(aval.shape), str(aval.dtype))
        if avail.get(key, 0) > 0:
            avail[key] -= 1
            missed += 1
            missed_bytes += _aval_bytes(aval)
    if missed:
        out.append(Finding("warning", R_MISSED_DONATION, site,
                           f"{missed} output buffer(s) ({missed_bytes} "
                           f"bytes) match non-donated inputs; donating "
                           f"would reuse them in place"))
    return out


def _canon_jaxpr(fn, args) -> str:
    import jax
    return str(jax.make_jaxpr(fn)(*args))


def audit_retrace(fn, args_a, args_b, site: str = "program") -> list[Finding]:
    """Differential capture check: trace ``fn`` at two consecutive engine
    states (same tree/shapes/dtypes, different values).  Identical jaxprs
    prove every tick-varying value flows through the arguments."""
    ja = _canon_jaxpr(fn, args_a)
    jb = _canon_jaxpr(fn, args_b)
    if ja == jb:
        return []
    delta = next((f"line {i}: {la!r} != {lb!r}" for i, (la, lb) in
                  enumerate(zip(ja.splitlines(), jb.splitlines()))
                  if la != lb), "program lengths differ")
    return [Finding("error", R_STATE_TRACE, site,
                    f"jaxpr differs across ticks ({delta})")]


def audit_jit_cache(jitted, ticks, site: str = "program") -> list[Finding]:
    """Dynamic recompile check: invoke the jitted callable on each tick's
    args (same shapes/dtypes throughout) and assert the compilation cache
    stops growing after the first call."""
    import jax

    if not hasattr(jitted, "_cache_size"):
        return [Finding("info", R_NO_INTROSPECTION, site,
                        "callable has no _cache_size()")]
    sizes = []
    for args in ticks:
        jax.block_until_ready(jitted(*args))
        sizes.append(jitted._cache_size())
    grew = [i for i in range(1, len(sizes)) if sizes[i] > sizes[i - 1]]
    if grew:
        return [Finding("error", R_RECOMPILE, site,
                        f"cache sizes {sizes} across same-shaped ticks: "
                        f"recompiled on tick(s) {grew}")]
    return []


# ---------------------------------------------------------------------------
# Shipped hot paths: the program set the repo serves/trains with
# ---------------------------------------------------------------------------


def audit_hot_paths(cfg, *, slots: int = 2, max_seq: int = 16,
                    page_size: int = 4, prompt_len: int = 4,
                    batch: int = 2) -> list[Finding]:
    """Audit the shipped serve decode / prefill / slot-write and train-step
    programs for ``cfg`` (use a reduced config: tracing is cheap but real).
    Encoder-only families audit the train step only.
    """
    import jax
    import jax.numpy as jnp

    from repro.launch.steps import make_train_step
    from repro.models import family_module
    from repro.optim import AdamW

    out: list[Finding] = []
    mod = family_module(cfg)
    key = jax.random.PRNGKey(0)
    params = mod.init(cfg, key, 1)

    # -- train step (jitted exactly as launch/train.py does) -----------------
    opt = AdamW()
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(cfg, opt, tp=1), donate_argnums=(0, 1))
    seq = 8
    mdt = jnp.dtype(cfg.dtype)
    lbl = jnp.zeros((batch, seq), jnp.int32)
    if cfg.embed_inputs:          # hubert: precomputed frame embeddings
        b = {"frames": jnp.zeros((batch, seq, cfg.d_model), mdt),
             "labels": lbl}
    elif cfg.vis_tokens:          # internvl2: patch-embedding prefix
        b = {"tokens": jnp.zeros((batch, seq), jnp.int32),
             "patches": jnp.zeros((batch, cfg.vis_tokens, cfg.d_model), mdt),
             "labels": lbl}
    else:
        b = {"tokens": jnp.zeros((batch, seq), jnp.int32), "labels": lbl}
    out += audit_program(step, params, opt_state, b,
                         donate_argnums=(0, 1),
                         site=f"{cfg.name}/train_step")
    if cfg.embed_inputs:
        return out

    # -- serving programs (the lru-cached builders the engines share) --------
    from repro.launch.serve import _jitted_steps, _paged_jitted_steps

    decode, prefill, write_slot = _jitted_steps(cfg, 1, "xla", max_seq)
    cache = mod.init_cache(cfg, slots, max_seq, 1)
    toks = np.zeros((slots, 1), np.int32)

    def dense_tick(t):
        return (params, cache, jnp.asarray(toks + t),
                jnp.asarray(np.full(slots, 1 + t), jnp.int32))

    out += audit_program(decode, *dense_tick(0),
                         site=f"{cfg.name}/serve_decode")
    out += audit_retrace(decode, dense_tick(0), dense_tick(1),
                         site=f"{cfg.name}/serve_decode")
    out += audit_jit_cache(decode, [dense_tick(0), dense_tick(1),
                                    dense_tick(2)],
                           site=f"{cfg.name}/serve_decode")

    ptoks = jnp.zeros((1, prompt_len), jnp.int32)
    out += audit_program(prefill, params, ptoks,
                         site=f"{cfg.name}/serve_prefill")
    out += audit_retrace(prefill, (params, ptoks), (params, ptoks + 1),
                         site=f"{cfg.name}/serve_prefill")

    _, pslot = jax.eval_shape(prefill, params, ptoks)
    slot_cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), pslot)
    out += audit_program(write_slot, cache, slot_cache, jnp.int32(0),
                         site=f"{cfg.name}/serve_write_slot")

    # -- paged decode (page-table KV) ----------------------------------------
    pdecode, _, _ = _paged_jitted_steps(cfg, 1, "xla")
    n_pages = -(-max_seq // page_size) * slots
    row_map = np.full((slots, max_seq), -1, np.int32)
    row_map[:, :page_size] = np.arange(
        slots * page_size, dtype=np.int32).reshape(slots, page_size)

    def paged_tick(t):
        # a cache of its own each tick: the program consumes (donates) it
        pcache = mod.init_paged_cache(cfg, slots, n_pages * page_size,
                                      max_seq, 1)
        return (params, pcache, jnp.asarray(toks + t),
                jnp.asarray(np.full(slots, 1 + t), jnp.int32),
                jnp.asarray(row_map))

    out += audit_program(pdecode, *paged_tick(0),
                         site=f"{cfg.name}/paged_decode")
    out += audit_retrace(pdecode, paged_tick(0), paged_tick(1),
                         site=f"{cfg.name}/paged_decode")
    out += audit_jit_cache(pdecode, [paged_tick(0), paged_tick(1),
                                     paged_tick(2)],
                           site=f"{cfg.name}/paged_decode")
    return out
