"""Jaxpr hot-path auditor (repro.analysis.jaxpr_audit, DESIGN.md §16.3).

  * shipped hot paths audit clean — ``audit_hot_paths`` over the real
    serve decode / chunked-prefill / slot-write / paged-decode / train-step
    programs must return no findings.  granite-moe pins the router-mask
    regression this auditor originally caught: the MoE padding mask was a
    weak-typed f32 constant, so a checkpoint round-trip (strong f32) vs a
    fresh init (weak f32) split the jit cache and silently recompiled
    every program that closed over it;
  * each detector fires on a minimal seeded program — host callbacks,
    state-dependent traces, silent recompiles, weak-typed args, scalar /
    large-array closure captures, and missed donations.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.findings import errors, warnings
from repro.analysis.jaxpr_audit import (audit_hot_paths, audit_jit_cache,
                                        audit_program, audit_retrace)
from repro.configs import get_config
from repro.models import reduced


def _rules(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# shipped hot paths are clean (regression pin for the router-mask fix)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["qwen3-8b", "granite-moe-3b-a800m"])
def test_shipped_hot_paths_audit_clean(arch):
    got = audit_hot_paths(reduced(get_config(arch)))
    assert got == [], [str(f) for f in got]


def test_encoder_only_audits_train_step_only():
    got = audit_hot_paths(reduced(get_config("hubert-xlarge")))
    assert got == [], [str(f) for f in got]


# ---------------------------------------------------------------------------
# seeded defects, one per detector
# ---------------------------------------------------------------------------

def test_host_callback_detected():
    def f(x):
        jax.debug.print("x={x}", x=x)
        return x * 2

    got = audit_program(f, jnp.zeros((4,), jnp.float32), site="t")
    assert "jaxpr/host-callback" in _rules(errors(got))


def test_clean_program_has_no_findings():
    def f(x, y):
        return x @ y

    got = audit_program(f, jnp.zeros((8, 8), jnp.float32),
                        jnp.zeros((8, 8), jnp.float32), site="t")
    assert got == []


def test_state_dependent_trace_detected():
    def step(toks, pos):
        return toks + pos

    toks = jnp.zeros((2,), jnp.int32)
    # engine state leaking through python scalars: the value class changes
    # the traced dtype, so consecutive ticks trace different programs
    got = audit_retrace(step, (toks, 0), (toks, 0.5), site="t")
    assert _rules(got) == {"jaxpr/state-dependent-trace"}
    assert got[0].severity == "error"
    # committed arrays carry the state through the arguments: clean
    assert audit_retrace(step, (toks, jnp.int32(0)), (toks, jnp.int32(1)),
                         site="t") == []


def test_silent_recompile_detected():
    jf = jax.jit(lambda x, s: x * s)
    z = jnp.zeros((4,), jnp.float32)
    got = audit_jit_cache(jf, [(z, 2), (z, 2.5)], site="t")
    assert _rules(got) == {"jaxpr/recompile"}
    jf2 = jax.jit(lambda x: x * 2)
    assert audit_jit_cache(jf2, [(z,), (z,), (z,)], site="t") == []


def test_weak_typed_arg_flagged():
    def f(x, s):
        return x * s

    got = audit_program(f, jnp.zeros((4,), jnp.float32), 2.0, site="t")
    assert "jaxpr/weak-type-arg" in _rules(warnings(got))
    assert audit_program(f, jnp.zeros((4,), jnp.float32),
                         jnp.float32(2.0), site="t") == []


def test_weak_scalar_closure_capture_detected():
    temperature = jnp.asarray(2.5)          # weak 0-d: the router-mask bug

    def f(x):
        return x * temperature

    got = audit_program(f, jnp.zeros((4,), jnp.float32), site="t")
    assert "jaxpr/scalar-capture" in _rules(errors(got))


def test_large_const_capture_flagged():
    table = np.ones((600, 600), np.float32)           # 1.44 MB

    def f(x):
        return x @ jnp.asarray(table)

    got = audit_program(f, jnp.zeros((600,), jnp.float32), site="t")
    assert "jaxpr/large-const-capture" in _rules(warnings(got))


def test_missed_donation_flagged_and_silenced_by_donation():
    def step(state, x):
        return state + x, (state * x).sum()

    state = jnp.zeros((256, 256), jnp.float32)        # 256 KiB
    x = jnp.float32(1.0)
    got = audit_program(step, state, x, site="t")
    assert "jaxpr/missed-donation" in _rules(warnings(got))
    assert audit_program(step, state, x, donate_argnums=(0,),
                         site="t") == []


def test_paged_decode_donates_its_cache():
    """The paged engine's decode program donates its cache, so the audit
    finds no missed donation there; the same step jitted without donation
    is flagged (the pool here is past the audit's size threshold)."""
    from repro.launch.serve import _paged_jitted_steps
    from repro.launch.steps import make_decode_step
    from repro.models import family_module

    cfg = reduced(get_config("qwen3-8b"))
    mod = family_module(cfg)
    slots, max_seq = 8, 128
    args = (mod.init(cfg, jax.random.PRNGKey(0), 1),
            mod.init_paged_cache(cfg, slots, slots * max_seq, max_seq, 1),
            jnp.zeros((slots, 1), jnp.int32), jnp.zeros((slots,), jnp.int32),
            jnp.zeros((slots, max_seq), jnp.int32))
    decode, _, _ = _paged_jitted_steps(cfg, 1, "xla")
    got = audit_program(decode, *args, site="paged_decode")
    assert "jaxpr/missed-donation" not in _rules(got), \
        [str(f) for f in got]
    kept = audit_program(jax.jit(make_decode_step(cfg, tp=1)), *args,
                         site="paged_decode")
    assert "jaxpr/missed-donation" in _rules(warnings(kept))
