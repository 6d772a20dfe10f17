"""What every family's plain reference shares: float32 numerics and where
the reference runs.

A family's reference (``families/<family>.py``) imports nothing of the
program.  It runs one full causal forward over each sequence, layer by
layer, with every matrix product at ``precision=highest`` through ``mm``.
It runs on the first chip: each layer's weights, the embedding and the head
are copied there before they are used (``on_first``, ``layer_weights``), so
a cell whose weights are sharded over several chips is checked the same
way as a cell on one chip.

``mode="fp8"`` puts the control in the reference's place: both operands of
every matrix product rounded to float8 e4m3, with one scale per row or
column along the contracted axes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _round(x, axes, mode):
    if mode is None:
        return x
    if mode != "fp8":
        raise ValueError(f"unknown control mode {mode!r}")
    scale = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(spec: str, a, b, mode=None):
    """``einsum(spec, a, b)`` in float32 at highest precision, both operands
    first rounded to ``mode`` along the axes the product contracts."""
    ins, out = spec.split("->")
    sa, sb = ins.split(",")
    contracted = set(sa) & set(sb) - set(out)
    a = _round(a.astype(jnp.float32),
               tuple(i for i, c in enumerate(sa) if c in contracted), mode)
    b = _round(b.astype(jnp.float32),
               tuple(i for i, c in enumerate(sb) if c in contracted), mode)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def rope(x, theta):
    """Rotary embedding, rotate-half form, on (B, S, H, D) at positions
    0..S-1."""
    s, half = x.shape[1], x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv    # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def on_first(tree):
    """``tree`` whole on the first device: a copy of what lies elsewhere or
    is sharded, the same arrays where they lie there already."""
    return jax.device_put(tree, jax.devices()[0])


@jax.jit
def _slice(stack, i):
    return jax.tree_util.tree_map(lambda a: a[i], stack)


def layer_weights(stack, i: int):
    """Layer ``i`` of a tree stacked over depth, on the first device."""
    return on_first(_slice(stack, i))


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def head(x, norm, table, *, eps, mode):
    """The final norm and the head's logits of rows ``x`` (N, d)."""
    return mm("nd,vd->nv", rms_norm(x, norm, eps), table, mode)
