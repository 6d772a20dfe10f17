"""Unified transformer family: dense GQA LMs (deepseek-coder-33b,
deepseek-67b, qwen3-8b, internvl2-76b backbone), gemma2 (alternating
local/global + softcaps + sandwich norms), MoE LMs (granite, moonshot), and
the hubert encoder — selected purely by ModelConfig flags.

Layers are scanned (jax.lax.scan) with optional remat so that 95-layer
configs stay compile-light; gemma2's local/global alternation scans pairs.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.distributed.context import constrain_activations
from . import layers as L
from .config import ModelConfig

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# One transformer block
# ---------------------------------------------------------------------------

def _block_init(key, cfg: ModelConfig, tp: int, dtype) -> Params:
    ks = jax.random.split(key, 3)
    p: Params = {
        "ln_attn": jnp.zeros((cfg.d_model,), dtype) if cfg.sandwich_norm
        else jnp.ones((cfg.d_model,), dtype),
        "ln_mlp": jnp.zeros((cfg.d_model,), dtype) if cfg.sandwich_norm
        else jnp.ones((cfg.d_model,), dtype),
        "attn": L.attn_init(ks[0], cfg, tp, dtype),
    }
    if cfg.sandwich_norm:
        p["ln_attn_post"] = jnp.zeros((cfg.d_model,), dtype)
        p["ln_mlp_post"] = jnp.zeros((cfg.d_model,), dtype)
    if cfg.n_experts:
        p["moe"] = L.moe_init(ks[1], cfg, tp, dtype)
    else:
        p["mlp"] = L.mlp_init(ks[2], cfg, dtype)
    return p


def _block_specs(cfg: ModelConfig) -> Params:
    p: Params = {"ln_attn": P(None), "ln_mlp": P(None),
                 "attn": L.attn_specs(cfg)}
    if cfg.sandwich_norm:
        p["ln_attn_post"] = P(None)
        p["ln_mlp_post"] = P(None)
    if cfg.n_experts:
        p["moe"] = L.moe_specs()
    else:
        p["mlp"] = L.mlp_specs()
    return p


def _block(p: Params, cfg: ModelConfig, x, *, positions, tp, impl, window,
           cache=None, layer=None, cache_pos=None, row_map=None):
    plus_one = cfg.sandwich_norm  # gemma-style (1+w) norms
    h = L.rms_norm(x, p["ln_attn"], plus_one=plus_one)
    attn_out, new_cache = L.attention(
        p["attn"], cfg, h, positions=positions, tp=tp, impl=impl,
        window=window, cache=cache, layer=layer, cache_pos=cache_pos,
        row_map=row_map)
    if cfg.sandwich_norm:
        attn_out = L.rms_norm(attn_out, p["ln_attn_post"], plus_one=True)
    x = x + attn_out
    h = L.rms_norm(x, p["ln_mlp"], plus_one=plus_one)
    if cfg.n_experts:
        mlp_out = L.moe(p["moe"], cfg, h, tp)
    else:
        mlp_out = L.mlp(p["mlp"], h, gelu=cfg.gelu_mlp)
    if cfg.sandwich_norm:
        mlp_out = L.rms_norm(mlp_out, p["ln_mlp_post"], plus_one=True)
    return constrain_activations(x + mlp_out), new_cache


# ---------------------------------------------------------------------------
# Whole model
# ---------------------------------------------------------------------------

def _n_groups(cfg: ModelConfig) -> int:
    return cfg.n_layers // 2 if cfg.alt_local_global else cfg.n_layers


def init(cfg: ModelConfig, key, tp: int = 1) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, cfg.n_layers + 3)
    # vmap over the layer keys: the same values as a per-layer loop, built
    # stacked (one layer's program to compile, and no unstacked copy)
    blocks = jax.vmap(lambda k: _block_init(k, cfg, tp, dtype))
    if cfg.alt_local_global:
        layers = {"local": blocks(keys[0:cfg.n_layers:2]),
                  "global": blocks(keys[1:cfg.n_layers:2])}
    else:
        layers = {"all": blocks(keys[:cfg.n_layers])}
    p: Params = {
        "embed": L.embed_init(keys[-3], cfg, tp, dtype),
        "layers": layers,
        "final_norm": (jnp.zeros if cfg.sandwich_norm else jnp.ones)(
            (cfg.d_model,), dtype),
    }
    if not cfg.name.startswith("gemma"):   # gemma ties head to the embedding
        p["head"] = {"table": L._normal(keys[-2], (cfg.padded(tp).vocab,
                                                   cfg.d_model), 0.02, dtype)}
    return p


def specs(cfg: ModelConfig) -> Params:
    blk = _block_specs(cfg)

    def stacked(tree):
        return jax.tree_util.tree_map(
            lambda s: P(None, *s), tree,
            is_leaf=lambda x: isinstance(x, P))

    if cfg.alt_local_global:
        layers = {"local": stacked(blk), "global": stacked(blk)}
    else:
        layers = {"all": stacked(blk)}
    p: Params = {"embed": L.embed_specs(), "layers": layers,
                 "final_norm": P(None)}
    if not cfg.name.startswith("gemma"):
        p["head"] = L.embed_specs()
    return p


def _run_layers(params, cfg: ModelConfig, x, *, positions, tp, impl,
                caches=None, cache_pos=None, row_map=None):
    """Scan the block stack; returns (x, new_caches).  ``row_map`` is the
    per-slot page table, shared by every paged layer (closure, not scanned).

    In decode the stacked caches ride in the scan's carry beside ``x`` and
    the layer index is scanned with the weights: each layer scatters its
    new rows into, and reads its rows from, the stacked leaves in place.
    Scanned as ``xs``/``ys`` instead, every layer would slice its whole
    cache out and write it back into a fresh stack."""
    decode = caches is not None

    def make_body(window):
        def body(carry, xs):
            if decode:
                x, cache = carry
                lp, i = xs
                x, cache = _block(lp, cfg, x, positions=positions, tp=tp,
                                  impl=impl, window=window, cache=cache,
                                  layer=i, cache_pos=cache_pos,
                                  row_map=row_map)
                return (x, cache), None
            x, _ = _block(xs, cfg, carry, positions=positions, tp=tp,
                          impl=impl, window=window)
            return x, None
        if cfg.remat and not decode:
            return jax.checkpoint(body)
        return body

    if cfg.alt_local_global:
        loc, glo = params["layers"]["local"], params["layers"]["global"]
        body_l = make_body(cfg.local_window)
        body_g = make_body(0)

        def pair(carry, xs):
            if decode:
                x, cl, cg = carry
                lpl, lpg, i = xs
                (x, cl), _ = body_l((x, cl), (lpl, i))
                (x, cg), _ = body_g((x, cg), (lpg, i))
                return (x, cl, cg), None
            lpl, lpg = xs
            x, _ = body_l(carry, lpl)
            x, _ = body_g(x, lpg)
            return x, None
        with jax.named_scope("layers"):
            if decode:
                (x, cl, cg), _ = jax.lax.scan(
                    pair, (x, caches["local"], caches["global"]),
                    (loc, glo, jnp.arange(_n_groups(cfg))))
                return x, {"local": cl, "global": cg}
            x, _ = jax.lax.scan(pair, x, (loc, glo))
        return x, None
    body = make_body(cfg.local_window)
    with jax.named_scope("layers"):
        if decode:
            (x, cache), _ = jax.lax.scan(
                body, (x, caches["all"]),
                (params["layers"]["all"], jnp.arange(cfg.n_layers)))
            return x, {"all": cache}
        x, _ = jax.lax.scan(body, x, params["layers"]["all"])
    return x, None


def _embed_inputs(params, cfg: ModelConfig, inputs: Params) -> jax.Array:
    scale = cfg.name.startswith("gemma")
    if cfg.embed_inputs:                       # hubert: precomputed frames
        return inputs["frames"]
    x = L.embed(params["embed"], inputs["tokens"], scale=scale)
    if cfg.vis_tokens:                         # internvl2: patch prefix
        x = jnp.concatenate([inputs["patches"].astype(x.dtype), x], axis=1)
    return x


def forward(params: Params, cfg: ModelConfig, inputs: Params, *,
            tp: int = 1, impl: str = "xla") -> jax.Array:
    """Full-sequence forward -> logits (train / prefill / encoder)."""
    x = _embed_inputs(params, cfg, inputs)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x, _ = _run_layers(params, cfg, x, positions=positions, tp=tp, impl=impl)
    x = L.rms_norm(x, params["final_norm"], plus_one=cfg.sandwich_norm)
    head = params.get("head", params["embed"])
    return L.unembed(head, x, cfg.vocab, cap=cfg.final_softcap)


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, tp: int = 1,
               dtype=jnp.bfloat16) -> Params:
    def one(n, seq):
        return jax.tree_util.tree_map(
            lambda x: jnp.zeros((n,) + x.shape, x.dtype),
            L.init_kv_cache(cfg, batch, seq, tp, dtype))
    if cfg.alt_local_global:
        n = cfg.n_layers // 2
        # sliding-window layers carry a ring buffer of `window` slots —
        # 8x smaller cache for gemma2 decode_32k (EXPERIMENTS.md §Perf)
        local_seq = min(max_seq, cfg.local_window or max_seq)
        return {"local": one(n, local_seq), "global": one(n, max_seq)}
    return {"all": one(cfg.n_layers, max_seq)}


def init_prefill_cache(cfg: ModelConfig, batch: int, seq: int, tp: int = 1,
                       dtype=jnp.bfloat16) -> Params:
    """Full-length caches for a one-shot slot prefill (DESIGN.md §11).

    Sliding-window layers get the whole sequence rather than their ring:
    during a single-forward prefill every query position must see its exact
    window, or mid-prompt activations (and through them the final token's
    deeper layers) silently degrade.  :func:`pack_slot_cache` folds the
    result back into the serving ring layout afterwards.
    """
    def one(n):
        return jax.tree_util.tree_map(
            lambda x: jnp.zeros((n,) + x.shape, x.dtype),
            L.init_kv_cache(cfg, batch, seq, tp, dtype))
    if cfg.alt_local_global:
        n = cfg.n_layers // 2
        return {"local": one(n), "global": one(n)}
    return {"all": one(cfg.n_layers)}


def pack_slot_cache(cfg: ModelConfig, pcache: Params, max_seq: int,
                    seq_len: int) -> Params:
    """Repack a batch-1 prefill cache (:func:`init_prefill_cache`, length
    ``seq_len``) into one slot of the serving cache layout: plain KV is
    right-padded to ``max_seq``; sliding-window groups are folded into their
    ring layout (slot ``p % window`` holds position ``p`` of the last
    ``window`` positions, exactly what sequential decode would have left)."""
    if seq_len > max_seq:
        raise ValueError(f"prompt length {seq_len} exceeds max_seq {max_seq}")

    def one(tree, target, use_ring):
        fn = (lambda x: _fold_ring(x, target, seq_len)) if use_ring else \
            (lambda x: _pad_rows(x, target))
        return jax.tree_util.tree_map(fn, tree)

    if cfg.alt_local_global:
        local_seq = min(max_seq, cfg.local_window or max_seq)
        return {"local": one(pcache["local"], local_seq,
                             local_seq == cfg.local_window),
                "global": one(pcache["global"], max_seq, False)}
    return {"all": one(pcache["all"], max_seq, False)}


def _pad_rows(leaf, target):
    if leaf.shape[2] == target:
        return leaf
    widths = [(0, 0)] * leaf.ndim
    widths[2] = (0, target - leaf.shape[2])
    return jnp.pad(leaf, widths)


def _fold_ring(leaf, window, seq_len):
    last = seq_len - 1
    j = np.arange(window)
    p = last - (last - j) % window              # absolute position per slot
    rows = jnp.take(leaf, jnp.asarray(np.clip(p, 0, seq_len - 1)), axis=2)
    valid = jnp.asarray(p >= 0).reshape(
        (1, 1, window) + (1,) * (leaf.ndim - 3))
    return jnp.where(valid, rows, jnp.zeros_like(rows))


def cache_slot_axes(cfg: ModelConfig) -> Params:
    """Batch(=slot)-axis index of every cache leaf — the scatter map the
    serving engine uses to write one slot's prefill into the shared cache."""
    one = jax.tree_util.tree_map(lambda _: 1, L.kv_cache_specs(cfg),
                                 is_leaf=lambda x: isinstance(x, P))
    if cfg.alt_local_global:
        return {"local": one, "global": one}
    return {"all": one}


def cache_specs(cfg: ModelConfig) -> Params:
    base = jax.tree_util.tree_map(
        lambda s: P(None, *s), L.kv_cache_specs(cfg),
        is_leaf=lambda x: isinstance(x, P))
    if cfg.alt_local_global:
        return {"local": base, "global": base}
    return {"all": base}


def init_paged_cache(cfg: ModelConfig, slots: int, rows: int, max_seq: int,
                     tp: int = 1, dtype=jnp.bfloat16) -> Params:
    """Paged serving cache (DESIGN.md §12): full-length attention KV lives
    in one physical pool of ``rows`` page-resident rows shared by every
    slot, indexed through the engine's page table.  Sliding-window ring
    layers keep their fixed per-slot ring — a ring is already O(window) per
    slot regardless of request length, so paging it frees nothing."""
    def pool(n):
        return jax.tree_util.tree_map(
            lambda x: jnp.zeros((n,) + x.shape, x.dtype),
            L.init_paged_kv_pool(cfg, rows, tp, dtype))

    def dense(n, seq):
        return jax.tree_util.tree_map(
            lambda x: jnp.zeros((n,) + x.shape, x.dtype),
            L.init_kv_cache(cfg, slots, seq, tp, dtype))

    if cfg.alt_local_global:
        n = cfg.n_layers // 2
        local_seq = min(max_seq, cfg.local_window or max_seq)
        return {"local": dense(n, local_seq), "global": pool(n)}
    return {"all": pool(cfg.n_layers)}


def paged_cache_specs(cfg: ModelConfig) -> Params:
    """Shardings mirroring :func:`init_paged_cache`: pool leaves gain the
    layer axis over the kv-pool specs; gemma2 local rings reuse the dense
    per-slot specs."""
    def stacked(tree):
        return jax.tree_util.tree_map(
            lambda s: P(None, *s), tree, is_leaf=lambda x: isinstance(x, P))

    pool = stacked(L.paged_kv_pool_specs(cfg))
    if cfg.alt_local_global:
        return {"local": stacked(L.kv_cache_specs(cfg)), "global": pool}
    return {"all": pool}


def paged_slot_axes(cfg: ModelConfig) -> Params:
    """Scatter map for the paged cache: ``"pool"`` marks leaves living in
    the shared physical pool (written through page-table rows); ints are
    the slot-axis index of per-slot dense leaves, as in
    :func:`cache_slot_axes`."""
    one = jax.tree_util.tree_map(lambda _: 1, L.kv_cache_specs(cfg),
                                 is_leaf=lambda x: isinstance(x, P))
    pool = jax.tree_util.tree_map(lambda _: "pool", L.kv_cache_specs(cfg),
                                  is_leaf=lambda x: isinstance(x, P))
    if cfg.alt_local_global:
        return {"local": one, "global": pool}
    return {"all": pool}


def pack_paged_slot(cfg: ModelConfig, pcache: Params, max_seq: int,
                    seq_len: int) -> Params:
    """Repack a batch-1 prefill cache for the paged layout: ring leaves are
    folded exactly as in :func:`pack_slot_cache`; pool leaves keep their raw
    ``seq_len`` rows — the engine scatters them at page-table rows, so no
    right-padding to ``max_seq`` ever exists (that padding is the per-slot
    memory the paged engine reclaims)."""
    if seq_len > max_seq:
        raise ValueError(f"prompt length {seq_len} exceeds max_seq {max_seq}")
    if cfg.alt_local_global:
        local_seq = min(max_seq, cfg.local_window or max_seq)
        if local_seq == cfg.local_window:
            local = jax.tree_util.tree_map(
                lambda x: _fold_ring(x, local_seq, seq_len), pcache["local"])
        else:
            local = jax.tree_util.tree_map(
                lambda x: _pad_rows(x, local_seq), pcache["local"])
        return {"local": local, "global": pcache["global"]}
    return {"all": pcache["all"]}


def decode_step(params: Params, cfg: ModelConfig, cache: Params,
                tokens: jax.Array, pos: jax.Array, *, tp: int = 1,
                impl: str = "xla",
                row_map: jax.Array | None = None) -> tuple[jax.Array, Params]:
    """One autoregressive step: tokens (B, S) at per-slot absolute positions
    ``pos`` — (B,) int32, a scalar broadcasts.  S=1 is the serving decode
    step; S>1 is a slot prefill (one causal forward whose K/V land in the
    cache at ``pos .. pos+S-1``).  ``row_map`` (B, L) routes pooled KV
    leaves through the paged engine's page table (DESIGN.md §12)."""
    scale = cfg.name.startswith("gemma")
    x = L.embed(params["embed"], tokens, scale=scale)
    b, s, _ = x.shape
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    positions = pos[:, None] + jnp.arange(s)
    x, new_cache = _run_layers(params, cfg, x, positions=positions, tp=tp,
                               impl=impl, caches=cache, cache_pos=pos,
                               row_map=row_map)
    with jax.named_scope("lm_head"):
        x = L.rms_norm(x, params["final_norm"], plus_one=cfg.sandwich_norm)
        head = params.get("head", params["embed"])
        logits = L.unembed(head, x, cfg.vocab, cap=cfg.final_softcap)
    return logits, new_cache
