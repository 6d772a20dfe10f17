"""Zamba2 hybrid LM (zamba2-2.7b): Mamba-2 backbone + a *shared* attention
block applied every ``attn_every`` mamba layers (weights reused across
invocations — the Zamba signature trick that buys attention quality at a
fraction of the parameter cost).

Mamba-2 mixer per layer: in_proj -> [z | x | B | C | dt], short causal
depthwise conv on (x|B|C), SSD recurrence via the chunked Pallas kernel with
per-head scalar decay a = dt·(−exp(A_log)), D skip, silu(z) gating, RMS norm,
out_proj.  Decode carries (conv tail, SSD state) — O(1) per token, so the
long_500k cell runs for this family.

Simplification noted in DESIGN.md: the shared block sees the hidden state
only (upstream Zamba2 concatenates the original embeddings) and LoRA
per-invocation adapters are omitted.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.context import constrain_activations
from repro.kernels import ops
from . import layers as L
from .config import ModelConfig

Params = dict[str, Any]
CONV_K = 4


def _dims(cfg: ModelConfig, tp: int):
    d_in = cfg.ssm_expand * cfg.d_model
    heads = cfg.padded(tp).ssm_heads or d_in // cfg.ssm_head_dim
    return d_in, heads, cfg.ssm_state


def _mamba_init(key, cfg: ModelConfig, tp: int, dtype) -> Params:
    d = cfg.d_model
    d_in, h, n = _dims(cfg, tp)
    hp = h * cfg.ssm_head_dim              # padded inner width
    ks = jax.random.split(key, 4)
    conv_dim = hp + 2 * n
    logical_h = (cfg.ssm_expand * d) // cfg.ssm_head_dim
    p = {
        "ln": jnp.ones((d,), dtype),
        # [z (hp) | x (hp) | B (n) | C (n) | dt (h)]
        "in_proj": L._normal(ks[0], (d, 2 * hp + 2 * n + h), d ** -0.5, dtype),
        "conv_w": L._normal(ks[1], (CONV_K, conv_dim), 0.3, dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "a_log": jnp.zeros((h,), jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "d_skip": jnp.ones((h,), jnp.float32),
        "norm": jnp.ones((hp,), dtype),
        "out_proj": L._normal(ks[2], (hp, d), hp ** -0.5, dtype),
    }
    if h > logical_h:  # exact padding: zero out_proj rows for extra heads
        mask = (jnp.arange(h) < logical_h).repeat(cfg.ssm_head_dim)[:, None]
        p["out_proj"] = (p["out_proj"] * mask).astype(dtype)
    return p


def _mamba_specs() -> Params:
    return {
        "ln": P(None), "in_proj": P(L.FSDP, L.TP),
        "conv_w": P(None, L.TP), "conv_b": P(L.TP),
        "a_log": P(L.TP), "dt_bias": P(L.TP), "d_skip": P(L.TP),
        "norm": P(L.TP), "out_proj": P(L.TP, L.FSDP),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 tail: jax.Array | None = None):
    """Depthwise causal conv, window CONV_K, via shift-and-add.
    x: (B, S, C); tail: (B, CONV_K-1, C) carry for decode.
    Returns (y, new_tail)."""
    if tail is None:
        tail = jnp.zeros((x.shape[0], CONV_K - 1, x.shape[2]), x.dtype)
    ext = jnp.concatenate([tail, x], axis=1)          # (B, S+K-1, C)
    s = x.shape[1]
    y = sum(ext[:, i:i + s] * w[i] for i in range(CONV_K)) + b
    return jax.nn.silu(y), ext[:, -(CONV_K - 1):]


def _mamba_block(p: Params, cfg: ModelConfig, x, tp: int, impl: str,
                 state: Params | None = None):
    bsz, s, d = x.shape
    d_in, h, n = _dims(cfg, tp)
    hp = h * cfg.ssm_head_dim
    ph = cfg.ssm_head_dim
    st = state or {}

    hx = L.rms_norm(x, p["ln"])
    zxbcdt = hx @ p["in_proj"]
    z = zxbcdt[..., :hp]
    xbc = zxbcdt[..., hp:hp + hp + 2 * n]
    dt_raw = zxbcdt[..., -h:]

    xbc, new_tail = _causal_conv(xbc, p["conv_w"], p["conv_b"],
                                 st.get("conv"))
    xs = xbc[..., :hp].reshape(bsz, s, h, ph)
    bmat = xbc[..., hp:hp + n]                        # (B, S, N), one group
    cmat = xbc[..., hp + n:]

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    log_decay = -dt * jnp.exp(p["a_log"])             # (B, S, H) <= 0
    x_scaled = (xs.astype(jnp.float32) * dt[..., None]).astype(x.dtype)
    bh = jnp.broadcast_to(bmat[:, :, None, :], (bsz, s, h, n)).astype(x.dtype)
    ch = jnp.broadcast_to(cmat[:, :, None, :], (bsz, s, h, n)).astype(x.dtype)

    if s == 1:
        # decode fast path: one SSD recurrence step
        h0 = st.get("ssd")
        if h0 is None:
            h0 = jnp.zeros((bsz, h, n, ph), jnp.float32)
        xf = x_scaled[:, 0].astype(jnp.float32)
        bf, cf = bh[:, 0].astype(jnp.float32), ch[:, 0].astype(jnp.float32)
        h1 = jnp.exp(log_decay[:, 0])[..., None, None] * h0 \
            + bf[..., :, None] * xf[..., None, :]
        y = jnp.einsum("bhn,bhnp->bhp", cf, h1)[:, None].astype(x.dtype)
        new_ssd = h1
    else:
        y, new_ssd = ops.mamba2(x_scaled, log_decay.astype(x.dtype), bh, ch,
                                st.get("ssd"), implementation=impl)

    y = y + xs * p["d_skip"].astype(x.dtype)[None, None, :, None]
    y = y.reshape(bsz, s, hp)
    y = L.rms_norm(y * jax.nn.silu(z), p["norm"])
    out = y @ p["out_proj"]
    return constrain_activations(x + out), {"conv": new_tail, "ssd": new_ssd}


# ---------------------------------------------------------------------------
# Model: groups of (attn_every mamba blocks) + one shared attention block
# ---------------------------------------------------------------------------

def _n_groups(cfg: ModelConfig) -> int:
    return max(1, cfg.n_layers // max(1, cfg.attn_every))


def init(cfg: ModelConfig, key, tp: int = 1) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, cfg.n_layers + 4)
    blocks = [_mamba_init(keys[i], cfg, tp, dtype)
              for i in range(cfg.n_layers)]
    g = _n_groups(cfg)
    per = cfg.n_layers // g
    grouped = [jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                      *blocks[i * per:(i + 1) * per])
               for i in range(g)]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *grouped)
    ks = jax.random.split(keys[-4], 3)
    shared = {
        "ln_attn": jnp.ones((cfg.d_model,), dtype),
        "ln_mlp": jnp.ones((cfg.d_model,), dtype),
        "attn": L.attn_init(ks[0], cfg, tp, dtype),
        "mlp": L.mlp_init(ks[1], cfg, dtype),
    }
    return {
        "embed": L.embed_init(keys[-3], cfg, tp, dtype),
        "layers": stacked,                       # (G, per, ...)
        "shared": shared,
        "final_norm": jnp.ones((cfg.d_model,), dtype),
        "head": {"table": L._normal(keys[-2], (cfg.padded(tp).vocab,
                                               cfg.d_model), 0.02, dtype)},
    }


def specs(cfg: ModelConfig) -> Params:
    blk = jax.tree_util.tree_map(lambda s: P(None, None, *s), _mamba_specs(),
                                 is_leaf=lambda x: isinstance(x, P))
    shared = {"ln_attn": P(None), "ln_mlp": P(None),
              "attn": L.attn_specs(cfg), "mlp": L.mlp_specs()}
    return {"embed": L.embed_specs(), "layers": blk, "shared": shared,
            "final_norm": P(None), "head": L.embed_specs()}


def _shared_attn(shared: Params, cfg: ModelConfig, x, *, positions, tp, impl,
                 cache=None, layer=None, cache_pos=None, row_map=None):
    h = L.rms_norm(x, shared["ln_attn"])
    att, new_cache = L.attention(shared["attn"], cfg, h, positions=positions,
                                 tp=tp, impl=impl, cache=cache, layer=layer,
                                 cache_pos=cache_pos, row_map=row_map)
    x = x + att
    x = x + L.mlp(shared["mlp"], L.rms_norm(x, shared["ln_mlp"]))
    return x, new_cache


def forward(params, cfg: ModelConfig, inputs, *, tp: int = 1,
            impl: str = "xla") -> jax.Array:
    x = L.embed(params["embed"], inputs["tokens"])
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    shared = params["shared"]

    def inner(x, lp):
        x, _ = _mamba_block(lp, cfg, x, tp, impl)
        return x, None

    if cfg.remat:  # per-block remat: one block's working set at a time
        inner = jax.checkpoint(inner)

    def group(x, gp):
        x, _ = jax.lax.scan(inner, x, gp)
        x, _ = _shared_attn(shared, cfg, x, positions=positions, tp=tp,
                            impl=impl)
        return x, None

    if cfg.remat:
        group = jax.checkpoint(group)
    x, _ = jax.lax.scan(group, x, params["layers"])
    x = L.rms_norm(x, params["final_norm"])
    return L.unembed(params["head"], x, cfg.vocab)


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, tp: int = 1,
               dtype=None) -> Params:
    dtype = dtype or jnp.dtype(cfg.dtype)
    d_in, h, n = _dims(cfg, tp)
    hp = h * cfg.ssm_head_dim
    g = _n_groups(cfg)
    per = cfg.n_layers // g
    return {
        "conv": jnp.zeros((g, per, batch, CONV_K - 1, hp + 2 * n), dtype),
        "ssd": jnp.zeros((g, per, batch, h, n, cfg.ssm_head_dim),
                         jnp.float32),
        "attn": jax.tree_util.tree_map(
            lambda x: jnp.zeros((g,) + x.shape, x.dtype),
            L.init_kv_cache(cfg, batch, max_seq, tp, dtype)),
    }


def cache_specs(cfg: ModelConfig) -> Params:
    kv = jax.tree_util.tree_map(lambda s: P(None, *s), L.kv_cache_specs(cfg),
                                is_leaf=lambda x: isinstance(x, P))
    return {"conv": P(None, None, L.BATCH_AXES, None, L.TP),
            "ssd": P(None, None, L.BATCH_AXES, L.TP, None, None),
            "attn": kv}


def init_prefill_cache(cfg: ModelConfig, batch: int, seq: int, tp: int = 1,
                       dtype=None) -> Params:
    """Batch-1 prefill caches (DESIGN.md §11): conv/SSD states are O(1) in
    sequence length, only the shared-attention KV needs the prompt length."""
    return init_cache(cfg, batch, seq, tp, dtype)


def pack_slot_cache(cfg: ModelConfig, pcache: Params, max_seq: int,
                    seq_len: int) -> Params:
    """Repack a batch-1 prefill cache into one serving slot: recurrent
    conv/SSD states carry over as-is, the attention KV pads to ``max_seq``."""
    if seq_len > max_seq:
        raise ValueError(f"prompt length {seq_len} exceeds max_seq {max_seq}")

    def pad(leaf):
        widths = [(0, 0)] * leaf.ndim
        widths[2] = (0, max_seq - leaf.shape[2])
        return jnp.pad(leaf, widths)

    return {"conv": pcache["conv"], "ssd": pcache["ssd"],
            "attn": jax.tree_util.tree_map(pad, pcache["attn"])}


def cache_slot_axes(cfg: ModelConfig) -> Params:
    """Batch(=slot)-axis index of every cache leaf (serving scatter map)."""
    return {"conv": 2, "ssd": 2,
            "attn": jax.tree_util.tree_map(lambda _: 1,
                                           L.kv_cache_specs(cfg),
                                           is_leaf=lambda x: isinstance(x, P))}


def init_paged_cache(cfg: ModelConfig, slots: int, rows: int, max_seq: int,
                     tp: int = 1, dtype=None) -> Params:
    """Paged serving cache (DESIGN.md §12): conv/SSD states stay per-slot
    (O(1) in sequence length — nothing to page), the shared-attention KV
    moves into one physical pool of ``rows`` rows shared across slots."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    d_in, h, n = _dims(cfg, tp)
    hp = h * cfg.ssm_head_dim
    g = _n_groups(cfg)
    per = cfg.n_layers // g
    return {
        "conv": jnp.zeros((g, per, slots, CONV_K - 1, hp + 2 * n), dtype),
        "ssd": jnp.zeros((g, per, slots, h, n, cfg.ssm_head_dim),
                         jnp.float32),
        "attn": jax.tree_util.tree_map(
            lambda x: jnp.zeros((g,) + x.shape, x.dtype),
            L.init_paged_kv_pool(cfg, rows, tp, dtype)),
    }


def paged_cache_specs(cfg: ModelConfig) -> Params:
    """Shardings mirroring :func:`init_paged_cache`: per-slot conv/SSD
    states keep their dense specs (minus the layer axis, plus the group
    axes); the pooled attention KV gains the group axis over the kv-pool
    specs."""
    pool = jax.tree_util.tree_map(
        lambda s: P(None, *s), L.paged_kv_pool_specs(cfg),
        is_leaf=lambda x: isinstance(x, P))
    return {"conv": P(None, None, L.BATCH_AXES, None, L.TP),
            "ssd": P(None, None, L.BATCH_AXES, L.TP, None, None),
            "attn": pool}


def paged_slot_axes(cfg: ModelConfig) -> Params:
    """Scatter map for the paged cache: ``"pool"`` marks pooled KV leaves,
    ints the slot-axis of per-slot recurrent leaves."""
    return {"conv": 2, "ssd": 2,
            "attn": jax.tree_util.tree_map(lambda _: "pool",
                                           L.kv_cache_specs(cfg),
                                           is_leaf=lambda x: isinstance(x, P))}


def pack_paged_slot(cfg: ModelConfig, pcache: Params, max_seq: int,
                    seq_len: int) -> Params:
    """Paged repack: recurrent states carry as-is; attention KV keeps its
    raw ``seq_len`` rows for the engine's page-table scatter (no padding)."""
    if seq_len > max_seq:
        raise ValueError(f"prompt length {seq_len} exceeds max_seq {max_seq}")
    return pcache


def decode_step(params, cfg: ModelConfig, cache, tokens, pos, *,
                tp: int = 1, impl: str = "xla", row_map=None):
    """Decode ``tokens (B, S)`` at per-slot positions ``pos`` ((B,) int32,
    scalar broadcasts); S>1 is a slot prefill.  ``row_map`` (B, L) routes
    the pooled attention KV through the paged engine's page table."""
    x = L.embed(params["embed"], tokens)
    b, s, _ = x.shape
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    positions = pos[:, None] + jnp.arange(s)
    shared = params["shared"]

    def inner(x, xs):
        lp, st = xs
        x, ns = _mamba_block(lp, cfg, x, tp, impl, state=st)
        return x, ns

    # the stacked attention KV rides in the carry and is updated in place
    # at the group's index (as in the transformer's layer scan)
    def group(carry, xs):
        x, attn = carry
        gp, gconv, gssd, g = xs
        x, ns = jax.lax.scan(inner, x, (gp, {"conv": gconv, "ssd": gssd}))
        x, attn = _shared_attn(shared, cfg, x, positions=positions, tp=tp,
                               impl=impl, cache=attn, layer=g, cache_pos=pos,
                               row_map=row_map)
        return (x, attn), (ns["conv"], ns["ssd"])

    (x, nattn), (nconv, nssd) = jax.lax.scan(
        group, (x, cache["attn"]),
        (params["layers"], cache["conv"], cache["ssd"],
         jnp.arange(cache["conv"].shape[0])))
    x = L.rms_norm(x, params["final_norm"])
    logits = L.unembed(params["head"], x, cfg.vocab)
    return logits, {"conv": nconv, "ssd": nssd, "attn": nattn}
