"""The plain reference: the served model's forward pass in float32.

It imports nothing of the program.  Its sizes come from the configuration
file (``configs/<config>.json``), its weights from ``weights.py``, which the
benchmark made.  One full causal forward over each sequence, layer by layer
and in blocks of queries so that it fits beside the weights, with every
matrix product at ``precision=highest``.  MoE layers route each token to its
top-k experts by the router's logits and weight them by the softmax over
those k; every expert is computed and the unrouted ones weighted 0.

The configuration's scalars are applied as Granite defines them, where the
file states them: ``embedding_multiplier`` on the embedded tokens,
``attention_multiplier`` as the attention scale (else ``head_dim ** -0.5``),
``residual_multiplier`` on each block's output before it joins the residual,
and the logits divided by ``logits_scaling``; ``tie_word_embeddings`` reads
the head from the embedding table.

``mode="fp8"`` puts the control in the reference's place: both operands of
every matrix product rounded to float8 e4m3, with one scale per row or
column along the contracted axes.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    theta: float
    eps: float
    qk_norm: bool
    experts: int = 0
    top_k: int = 0
    attn_scale: float = 0.0     # 0: head_dim ** -0.5
    embed_mult: float = 1.0
    resid_mult: float = 1.0
    logits_div: float = 1.0
    tied: bool = False

    @classmethod
    def of(cls, config: dict) -> "Dims":
        c = config["config"]
        return cls(attn_scale=float(c.get("attention_multiplier", 0.0)),
                   embed_mult=float(c.get("embedding_multiplier", 1.0)),
                   resid_mult=float(c.get("residual_multiplier", 1.0)),
                   logits_div=float(c.get("logits_scaling", 1.0)),
                   tied=bool(c.get("tie_word_embeddings", False)),
                   d=c["hidden_size"], layers=c["num_hidden_layers"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   ff=c["intermediate_size"], vocab=c["vocab_size"],
                   theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]),
                   qk_norm=config["model_type"] == "qwen3",
                   experts=c.get("num_local_experts", 0),
                   top_k=c.get("num_experts_per_tok", 0))


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


@functools.partial(jax.jit, static_argnames=("dims", "mode", "block"))
def layer(x, w, *, dims: Dims, mode, block: int):
    """One pre-norm block: x + attn(norm(x)), then + ffn(norm(.))."""
    b, s, _ = x.shape
    hd, kv, g = dims.head_dim, dims.kv_heads, dims.heads // dims.kv_heads
    a = w["attn"]
    h = rms_norm(x, w["ln_attn"], dims.eps)
    q = mm("bsd,dk->bsk", h, a["wq"], mode).reshape(b, s, dims.heads, hd)
    k = mm("bsd,dk->bsk", h, a["wk"], mode).reshape(b, s, kv, hd)
    v = mm("bsd,dk->bsk", h, a["wv"], mode).reshape(b, s, kv, hd)
    if dims.qk_norm:
        q = rms_norm(q, a["q_norm"], dims.eps)
        k = rms_norm(k, a["k_norm"], dims.eps)
    q, k = rope(q, dims.theta), rope(k, dims.theta)
    q = q.reshape(b, s, kv, g, hd)
    outs = []
    for lo in range(0, s, block):       # causal: block [lo, hi) sees :hi
        hi = min(s, lo + block)
        logits = mm("bqkgd,btkd->bkgqt", q[:, lo:hi], k[:, :hi], mode)
        logits = logits * (dims.attn_scale or hd ** -0.5)
        causal = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        p = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
        outs.append(mm("bkgqt,btkd->bqkgd", p, v[:, :hi], mode))
    o = jnp.concatenate(outs, axis=1).reshape(b, s, dims.heads * hd)
    x = x + dims.resid_mult * mm("bsk,kd->bsd", o, a["wo"], mode)
    h = rms_norm(x, w["ln_mlp"], dims.eps)
    if dims.experts:
        m = w["moe"]
        logits = mm("bsd,de->bse", h, m["router"], mode)
        top, idx = jax.lax.top_k(logits, dims.top_k)
        gates = jax.nn.softmax(top, axis=-1)
        weight = jnp.sum(jax.nn.one_hot(idx, dims.experts) * gates[..., None],
                         axis=-2)                              # (B, S, E)
        act = (jax.nn.silu(mm("bsd,edf->bsef", h, m["w_gate"], mode))
               * mm("bsd,edf->bsef", h, m["w_up"], mode))
        ffn = mm("bsef,efd->bsd", act * weight[..., None], m["w_down"], mode)
    else:
        m = w["mlp"]
        act = (jax.nn.silu(mm("bsd,df->bsf", h, m["w_gate"], mode))
               * mm("bsd,df->bsf", h, m["w_up"], mode))
        ffn = mm("bsf,fd->bsd", act, m["w_down"], mode)
    return x + dims.resid_mult * ffn


@jax.jit
def _layer_weights(stack, i):
    return jax.tree_util.tree_map(lambda a: a[i], stack)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def head(x, norm, table, *, eps, mode):
    return mm("nd,vd->nv", rms_norm(x, norm, eps), table, mode)


def logits(weights, dims: Dims, seqs: list[np.ndarray],
           at: list[np.ndarray], *, pad_to: int, mode=None,
           block: int = 512) -> list[np.ndarray]:
    """Float32 logits of each sequence ``seqs[i]`` at positions ``at[i]``.
    Sequences are right-padded to ``pad_to``; causal attention keeps the
    padding out of every real position."""
    tokens = np.zeros((len(seqs), pad_to), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = s
    x = jnp.take(weights["embed"]["table"], jnp.asarray(tokens),
                 axis=0).astype(jnp.float32) * dims.embed_mult
    stack = weights["layers"]["all"]
    for i in range(dims.layers):
        x = layer(x, _layer_weights(stack, i), dims=dims, mode=mode,
                  block=min(block, pad_to))
    rows = np.concatenate([np.full(len(a), i) for i, a in enumerate(at)])
    cols = np.concatenate(at)
    n = len(rows)
    pad = -n % 256      # a few head shapes, each compiled once
    rows, cols = np.pad(rows, (0, pad), mode="edge"), np.pad(cols, (0, pad),
                                                             mode="edge")
    table = weights["embed" if dims.tied else "head"]["table"]
    out = np.asarray(head(x[rows, cols], weights["final_norm"], table,
                          eps=dims.eps, mode=mode)) / dims.logits_div
    return np.split(out[:n], np.cumsum([len(a) for a in at])[:-1])
