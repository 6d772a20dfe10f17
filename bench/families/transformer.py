"""Family ``transformer``: the program's decoder-only transformer
(``repro.models.transformer``) with GQA attention, qk-norm where the model
has it, and after it either a SwiGLU MLP or a MoE layer that routes each
token to its top-k experts by a softmax over their k router logits.

What the harness asks of a family (``PERF.md`` §3):

- ``model_config(config)``: the program's ModelConfig for a configuration
  file, checked against every published size the file states;
- ``tree(cfg, key)``: the weights from a key, laid out as the program
  takes them (``weights.py`` builds them in one jitted call);
- ``Dims.of(config)``, ``layer`` and ``logits``: the float32 reference, and
  with ``mode="fp8"`` the control, through ``reference.mm``;
- ``decode_step(cfg, contexts)`` and ``prefill_chunk(cfg, start, tokens,
  last)``: the operations and bytes the roofline and MFU readers count.

The reference imports nothing of the program.  Its sizes come from the
configuration file, its weights from ``tree``, which the benchmark made.
Every expert is computed and the unrouted ones weighted 0.  The
configuration's scalars are applied as Granite defines them, where the file
states them: ``embedding_multiplier`` on the embedded tokens,
``attention_multiplier`` as the attention scale (else ``head_dim ** -0.5``),
``residual_multiplier`` on each block's output before it joins the
residual, and the logits divided by ``logits_scaling``;
``tie_word_embeddings`` reads the head from the embedding table.

The counts are the yardstick's, not the program's: they count the work the
model's equations require (routed experts only, the head only where a logit
is used, each weight and each live KV row read once), so a program that
pads, recomputes or re-reads shows as a lower share of the roofline.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import head, layer_weights, mm, on_first, rms_norm, \
    rope

BF16 = 2

# published (Hugging Face) key -> field of the program's ModelConfig
_HF_TO_PROGRAM = {
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "num_local_experts": "n_experts",
    "num_experts_per_tok": "top_k",
    "vocab_size": "vocab",
    "rope_theta": "rope_theta",
}


# -- the program's configuration ---------------------------------------------

def model_config(config: dict):
    """The program's ModelConfig for a configuration file: the registry's
    entry for ``arch`` with the file's ``overrides``, checked against every
    published size the file states, so the file and the run cannot drift."""
    from repro.configs import get_config

    cfg = dataclasses.replace(get_config(config["arch"]),
                              **config.get("overrides", {}))
    if cfg.n_experts:   # the published expert width is intermediate_size
        want_ff = ("moe_d_ff", config["config"]["intermediate_size"])
    else:
        want_ff = ("d_ff", config["config"]["intermediate_size"])
    checks = [(_HF_TO_PROGRAM[k], v) for k, v in config["config"].items()
              if k in _HF_TO_PROGRAM] + [want_ff]
    for field, want in checks:
        got = getattr(cfg, field)
        if got != want:
            raise ValueError(f"{config['arch']}: the program runs "
                             f"{field}={got}, the configuration file states "
                             f"{want}")
    if cfg.dtype != config["dtype"]:
        raise ValueError(f"{config['arch']}: program dtype {cfg.dtype}, "
                         f"file states {config['dtype']}")
    return cfg


# -- the weights --------------------------------------------------------------

def tree(cfg, key):
    """The weights of ``cfg`` from ``key``, in the dtype they are served in,
    as the program's transformer takes them (``layers.all`` stacked over
    depth).  Scales follow the usual init: ``N(0, 1/fan_in)`` for
    projections, ``N(0, 0.02²)`` for the embedding and the head, and norm
    gains ``1 + N(0, 0.1²)`` so that a norm left out shows."""
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


# -- the reference ---------------------------------------------------------

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


def logits(weights, dims: Dims, seqs: list[np.ndarray],
           at: list[np.ndarray], *, pad_to: int, mode=None,
           block: int = 512) -> list[np.ndarray]:
    """Float32 logits of each sequence ``seqs[i]`` at positions ``at[i]``,
    on the first device.  Sequences are right-padded to ``pad_to``; causal
    attention keeps the padding out of every real position."""
    tokens = np.zeros((len(seqs), pad_to), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = s
    x = jnp.take(on_first(weights["embed"]["table"]), jnp.asarray(tokens),
                 axis=0).astype(jnp.float32) * dims.embed_mult
    stack = weights["layers"]["all"]
    for i in range(dims.layers):
        x = layer(x, layer_weights(stack, i), dims=dims, mode=mode,
                  block=min(block, pad_to))
    rows = np.concatenate([np.full(len(a), i) for i, a in enumerate(at)])
    cols = np.concatenate(at)
    n = len(rows)
    pad = -n % 256      # a few head shapes, each compiled once
    rows, cols = np.pad(rows, (0, pad), mode="edge"), np.pad(cols, (0, pad),
                                                             mode="edge")
    table = weights["embed" if dims.tied else "head"]["table"]
    out = np.asarray(head(x[rows, cols], on_first(weights["final_norm"]),
                          on_first(table), eps=dims.eps, mode=mode)) \
        / dims.logits_div
    return np.split(out[:n], np.cumsum([len(a) for a in at])[:-1])


# -- the counts ---------------------------------------------------------------

def kv_bytes_per_token(cfg) -> int:
    """Keys and values of one token over every layer, in bf16."""
    return 2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim * BF16


def _proj_params(cfg) -> int:
    d, hd = cfg.d_model, cfg.head_dim
    return d * (cfg.n_heads + 2 * cfg.n_kv_heads) * hd + cfg.n_heads * hd * d


def layer_flops(cfg, context: int) -> float:
    """One token through one layer, attending over ``context`` keys."""
    attn = 2 * _proj_params(cfg) + 4 * cfg.n_heads * cfg.head_dim * context
    if cfg.n_experts:
        ffn = (2 * 3 * cfg.d_model * cfg.moe_d_ff * cfg.top_k
               + 2 * cfg.d_model * cfg.n_experts)
    else:
        ffn = 2 * 3 * cfg.d_model * cfg.d_ff
    return float(attn + ffn)


def token_flops(cfg, context: int, logits: bool) -> float:
    """One token through the model; the head only where its logits are
    used (the last prompt token, and every decoded token)."""
    head = 2 * cfg.d_model * cfg.vocab if logits else 0
    return cfg.n_layers * layer_flops(cfg, context) + head


def moe_padded_flops(cfg, tokens: int) -> float:
    """The expert einsums as the program runs them for ``tokens`` tokens of
    one sequence: every expert's whole capacity, ``max(8, ceil(s*k/e*cf))``
    rows, per layer (``layers.moe``)."""
    cap = max(8, math.ceil(tokens * cfg.top_k / cfg.n_experts
                           * cfg.capacity_factor))
    return 2.0 * 3 * cfg.d_model * cfg.moe_d_ff * cfg.n_experts * cap


def moe_routed_flops(cfg, tokens: int) -> float:
    return 2.0 * 3 * cfg.d_model * cfg.moe_d_ff * cfg.top_k * tokens


def weight_bytes(cfg, experts_touched: float | None = None) -> float:
    """Weights one step reads once; ``experts_touched`` of each MoE layer's
    experts (all of them when None)."""
    d = cfg.d_model
    per_layer = _proj_params(cfg) + 2 * d
    if cfg.qk_norm:
        per_layer += 2 * cfg.head_dim
    if cfg.n_experts:
        e = cfg.n_experts if experts_touched is None else experts_touched
        per_layer += 3 * d * cfg.moe_d_ff * e
        per_layer_f32 = d * cfg.n_experts + cfg.n_experts   # router, mask
    else:
        per_layer += 3 * d * cfg.d_ff
        per_layer_f32 = 0
    return (cfg.n_layers * (per_layer * BF16 + per_layer_f32 * 4)
            + (cfg.vocab * d + d) * BF16)   # head and final norm


def experts_touched(cfg, tokens: int) -> float:
    """Expected number of distinct experts ``tokens`` tokens route to, each
    picking ``top_k`` distinct experts of ``n_experts`` at random."""
    if not cfg.n_experts:
        return 0.0
    e, k = cfg.n_experts, cfg.top_k
    return e * (1.0 - (1.0 - k / e) ** tokens)


def decode_step(cfg, contexts: list[int]) -> tuple[float, float]:
    """(FLOPs, bytes) that one batched decode step needs: one token per live
    slot, slot ``i`` attending over ``contexts[i]`` keys.  Bytes: each weight
    the batch uses once, the embedding rows of the batch, every live KV row
    read once and the new rows written once."""
    flops = sum(token_flops(cfg, c, True) for c in contexts)
    b = len(contexts)
    nbytes = (weight_bytes(cfg, experts_touched(cfg, b) if cfg.n_experts
                           else None)
              + b * cfg.d_model * BF16
              + kv_bytes_per_token(cfg) * sum(contexts))
    return flops, nbytes


def prefill_chunk(cfg, start: int, tokens: int, last: bool) -> float:
    """FLOPs of one prefill chunk: ``tokens`` prompt tokens from position
    ``start``; ``last`` when it holds the prompt's last token (whose logits
    seed decoding)."""
    flops = sum(token_flops(cfg, start + i + 1, False) for i in range(tokens))
    return flops + (2 * cfg.d_model * cfg.vocab if last else 0)
