"""Random weights from the seed, made by the benchmark on the device.

One jitted call builds the whole tree in the dtype it is served in, laid out
as the program's transformer takes it (``layers.all`` stacked over depth).
The benchmark makes the weights so that the reference (``reference.py``)
never takes anything the program made.  Scales follow the usual init:
``N(0, 1/fan_in)`` for projections, ``N(0, 0.02²)`` for the embedding and
the head, and norm gains ``1 + N(0, 0.1²)`` so that a norm left out shows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _tree(cfg, key):
    d, hd, L = cfg.d_model, cfg.head_dim, cfg.n_layers
    h, kv, v = cfg.n_heads, cfg.n_kv_heads, cfg.vocab
    dtype = jnp.dtype(cfg.dtype)
    keys = iter(jax.random.split(key, 16))

    def normal(shape, scale, dt=dtype):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dt)

    def gain(shape):
        return (1.0 + normal(shape, 0.1, jnp.float32)).astype(dtype)

    attn = {"wq": normal((L, d, h * hd), d ** -0.5),
            "wk": normal((L, d, kv * hd), d ** -0.5),
            "wv": normal((L, d, kv * hd), d ** -0.5),
            "wo": normal((L, h * hd, d), (h * hd) ** -0.5)}
    if cfg.qk_norm:
        attn["q_norm"] = gain((L, hd))
        attn["k_norm"] = gain((L, hd))
    layer = {"ln_attn": gain((L, d)), "ln_mlp": gain((L, d)), "attn": attn}
    if cfg.n_experts:
        e, f = cfg.n_experts, cfg.moe_d_ff
        layer["moe"] = {
            "router": normal((L, d, e), d ** -0.5, jnp.float32),
            "router_mask": jnp.zeros((L, e), jnp.float32),
            "w_gate": normal((L, e, d, f), d ** -0.5),
            "w_up": normal((L, e, d, f), d ** -0.5),
            "w_down": normal((L, e, f, d), f ** -0.5)}
    else:
        f = cfg.d_ff
        layer["mlp"] = {"w_gate": normal((L, d, f), d ** -0.5),
                        "w_up": normal((L, d, f), d ** -0.5),
                        "w_down": normal((L, f, d), f ** -0.5)}
    return {"embed": {"table": normal((v, d), 0.02)},
            "layers": {"all": layer},
            "final_norm": gain((d,)),
            "head": {"table": normal((v, d), 0.02)}}


def make(cfg, seed: int):
    """The weights of ``cfg`` for ``seed``, on the default device."""
    key = jax.random.key(seed % 2**32 ^ (seed >> 32))
    return jax.jit(functools.partial(_tree, cfg))(key)


def shapes(cfg):
    """The weights' shapes and dtypes, with nothing allocated."""
    return jax.eval_shape(functools.partial(_tree, cfg), jax.random.key(0))
