"""Operations and bytes that the served model needs, from its shapes alone.

These are the yardstick's counts, not the program's: they count the work the
model's equations require (routed experts only, the head only where a logit
is used, each weight and each live KV row read once), so a program that pads,
recomputes or re-reads shows as a lower share of the roofline.
"""
from __future__ import annotations

import math

BF16 = 2


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
