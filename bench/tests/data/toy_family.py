"""Family ``toydense``, for the tests alone: a dense llama-style decoder
(GQA without qk-norm, SwiGLU) served by the program's transformer, with a
reference and counts of its own.  The tests place it under a benchmark
root's ``bench/families/`` to show that a family added as files runs end to
end without an edit to the harness."""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import head, layer_weights, mm, on_first, rms_norm, \
    rope

_KEYS = {"hidden_size": "d_model", "num_hidden_layers": "n_layers",
         "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
         "head_dim": "head_dim", "intermediate_size": "d_ff",
         "vocab_size": "vocab", "rope_theta": "rope_theta"}


def model_config(config: dict):
    from repro.configs import get_config

    cfg = dataclasses.replace(get_config(config["arch"]),
                              **config["overrides"])
    for key, field in _KEYS.items():
        if getattr(cfg, field) != config["config"][key]:
            raise ValueError(f"{key}: the program runs {field}="
                             f"{getattr(cfg, field)}")
    if cfg.n_experts or cfg.qk_norm:
        raise ValueError("toydense is dense and has no qk-norm")
    return cfg


def tree(cfg, key):
    d, hd, L, f = cfg.d_model, cfg.head_dim, cfg.n_layers, cfg.d_ff
    keys = iter(jax.random.split(key, 12))

    def normal(shape, scale):
        return (jax.random.normal(next(keys), shape) * scale).astype(
            jnp.bfloat16)

    def gain(shape):
        return (1.0 + jax.random.normal(next(keys), shape) * 0.1).astype(
            jnp.bfloat16)

    layer = {"ln_attn": gain((L, d)), "ln_mlp": gain((L, d)),
             "attn": {"wq": normal((L, d, cfg.n_heads * hd), d ** -0.5),
                      "wk": normal((L, d, cfg.n_kv_heads * hd), d ** -0.5),
                      "wv": normal((L, d, cfg.n_kv_heads * hd), d ** -0.5),
                      "wo": normal((L, cfg.n_heads * hd, d),
                                   (cfg.n_heads * hd) ** -0.5)},
             "mlp": {"w_gate": normal((L, d, f), d ** -0.5),
                     "w_up": normal((L, d, f), d ** -0.5),
                     "w_down": normal((L, f, d), f ** -0.5)}}
    return {"embed": {"table": normal((cfg.vocab, d), 0.02)},
            "layers": {"all": layer}, "final_norm": gain((d,)),
            "head": {"table": normal((cfg.vocab, d), 0.02)}}


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    theta: float
    eps: float

    @classmethod
    def of(cls, config: dict) -> "Dims":
        c = config["config"]
        return cls(c["num_hidden_layers"], c["num_attention_heads"],
                   c["num_key_value_heads"], c["head_dim"],
                   float(c["rope_theta"]), float(c["rms_norm_eps"]))


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def layer(x, w, *, dims: Dims, mode):
    b, s, _ = x.shape
    hd, kv = dims.head_dim, dims.kv_heads
    g = dims.heads // kv
    h = rms_norm(x, w["ln_attn"], dims.eps)
    a = w["attn"]
    q = rope(mm("bsd,dk->bsk", h, a["wq"], mode).reshape(b, s, dims.heads,
                                                         hd), dims.theta)
    k = rope(mm("bsd,dk->bsk", h, a["wk"], mode).reshape(b, s, kv, hd),
             dims.theta)
    v = mm("bsd,dk->bsk", h, a["wv"], mode).reshape(b, s, kv, hd)
    logits = mm("bqkgd,btkd->bkgqt", q.reshape(b, s, kv, g, hd), k, mode)
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, logits * hd ** -0.5, -jnp.inf), -1)
    o = mm("bkgqt,btkd->bqkgd", p, v, mode).reshape(b, s, dims.heads * hd)
    x = x + mm("bsk,kd->bsd", o, a["wo"], mode)
    h = rms_norm(x, w["ln_mlp"], dims.eps)
    m = w["mlp"]
    act = (jax.nn.silu(mm("bsd,df->bsf", h, m["w_gate"], mode))
           * mm("bsd,df->bsf", h, m["w_up"], mode))
    return x + mm("bsf,fd->bsd", act, m["w_down"], mode)


def logits(weights, dims: Dims, seqs, at, *, pad_to: int, mode=None):
    tokens = np.zeros((len(seqs), pad_to), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = s
    x = jnp.take(on_first(weights["embed"]["table"]), jnp.asarray(tokens),
                 axis=0).astype(jnp.float32)
    for i in range(dims.layers):
        x = layer(x, layer_weights(weights["layers"]["all"], i), dims=dims,
                  mode=mode)
    rows = np.concatenate([np.full(len(a), i) for i, a in enumerate(at)])
    out = np.asarray(head(x[rows, np.concatenate(at)],
                          on_first(weights["final_norm"]),
                          on_first(weights["head"]["table"]), eps=dims.eps,
                          mode=mode))
    return np.split(out, np.cumsum([len(a) for a in at])[:-1])


def _token_flops(cfg, context: int) -> float:
    d, hd = cfg.d_model, cfg.head_dim
    proj = d * (2 * cfg.n_heads + 2 * cfg.n_kv_heads) * hd
    return cfg.n_layers * (2 * proj + 4 * cfg.n_heads * hd * context
                           + 6 * d * cfg.d_ff)


def decode_step(cfg, contexts):
    d, hd = cfg.d_model, cfg.head_dim
    params = cfg.n_layers * (d * (2 * cfg.n_heads + 2 * cfg.n_kv_heads) * hd
                             + 3 * d * cfg.d_ff) + cfg.vocab * d
    kv = 2 * cfg.n_layers * cfg.n_kv_heads * hd * 2
    flops = sum(_token_flops(cfg, c) + 2 * d * cfg.vocab for c in contexts)
    return flops, 2 * params + kv * sum(contexts)


def prefill_chunk(cfg, start, tokens, last):
    return sum(_token_flops(cfg, start + i + 1) for i in range(tokens)) \
        + (2 * cfg.d_model * cfg.vocab if last else 0)
