"""Shared layer library for all architecture families.

Conventions:
  * params are nested dicts of jnp arrays; every init has a twin ``*_specs``
    returning the same tree with PartitionSpec leaves (tested for structural
    equality) — the dry-run shards straight from these.
  * ``tp`` (model-axis size) drives exactness-preserving padding of heads /
    kv-heads / experts / vocab (DESIGN.md §5).
  * ``impl`` selects the compute path: 'xla' (dry-run/roofline), 'interpret'
    (Pallas correctness on CPU), 'pallas' (real TPU).
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ops
from .config import ModelConfig

Params = dict[str, Any]

# logical mesh axes (DESIGN.md §6): batch over (pod, data), tensor over model
BATCH_AXES = ("pod", "data")
FSDP = "data"
TP = "model"


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Norms / activations / rope
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, w: jax.Array, *, eps: float = 1e-6,
             plus_one: bool = False) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    xf = xf * jax.lax.rsqrt(var + eps)
    scale = (1.0 + w.astype(jnp.float32)) if plus_one else w.astype(jnp.float32)
    return (xf * scale).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D) rotary on last dim; positions: (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs       # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1).astype(x.dtype)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    if cap <= 0:
        return x
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------

def embed_init(key, cfg: ModelConfig, tp: int, dtype) -> Params:
    v = cfg.padded(tp).vocab
    return {"table": _normal(key, (v, cfg.d_model), 0.02, dtype)}


def embed_specs() -> Params:
    # vocab over TP, d_model over FSDP: embedding optimizer moments are the
    # single biggest per-device residents otherwise (dry-run probe evidence)
    return {"table": P(TP, FSDP)}


@jax.named_scope("embed")
def embed(params: Params, tokens: jax.Array, scale: bool = False) -> jax.Array:
    out = jnp.take(params["table"], tokens, axis=0)
    if scale:
        out = out * math.sqrt(out.shape[-1])
    return out


def unembed(params: Params, x: jax.Array, vocab: int,
            cap: float = 0.0) -> jax.Array:
    logits = jnp.einsum("bsd,vd->bsv", x, params["table"])
    logits = softcap(logits, cap)
    v_pad = params["table"].shape[0]
    if v_pad > vocab:  # padded vocab rows never win
        mask = jnp.arange(v_pad) < vocab
        logits = jnp.where(mask, logits, -1e30)
    return logits


# ---------------------------------------------------------------------------
# Dense attention (GQA + qk-norm + softcap + sliding window + KV cache)
# ---------------------------------------------------------------------------

def attn_init(key, cfg: ModelConfig, tp: int, dtype) -> Params:
    pd = cfg.padded(tp)
    d, hd = cfg.d_model, cfg.head_dim
    h, kv = pd.n_heads, pd.n_kv_heads
    ks = jax.random.split(key, 4)
    sc = d ** -0.5
    p = {
        "wq": _normal(ks[0], (d, h * hd), sc, dtype),
        "wk": _normal(ks[1], (d, kv * hd), sc, dtype),
        "wv": _normal(ks[2], (d, kv * hd), sc, dtype),
        "wo": _normal(ks[3], (h * hd, d), (h * hd) ** -0.5, dtype),
    }
    # zero the padded q-heads' output rows -> exact at initialization
    if h > cfg.n_heads:
        mask = (jnp.arange(h) < cfg.n_heads).repeat(hd)[:, None]
        p["wo"] = (p["wo"] * mask).astype(dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def attn_specs(cfg: ModelConfig) -> Params:
    p = {"wq": P(FSDP, TP), "wk": P(FSDP, TP), "wv": P(FSDP, TP),
         "wo": P(TP, FSDP)}
    if cfg.qk_norm:
        p["q_norm"] = P(None)
        p["k_norm"] = P(None)
    return p


def _split_heads(x, n, hd):
    b, s, _ = x.shape
    return x.reshape(b, s, n, hd)


def gather_pages(pool: jax.Array, row_map: jax.Array,
                 layer: jax.Array | None = None) -> jax.Array:
    """Page-table gather: physical pool ``(R, ...)`` -> per-slot dense view
    ``(B, L, ...)`` where row ``i`` of slot ``b`` is ``pool[row_map[b, i]]``.
    Unmapped rows (``-1``) read as zeros, so the view is bit-identical to
    the dense ``(B, L, ...)`` cache layout the non-paged engine carries.
    With ``layer``, ``pool`` is the layer-stacked ``(n, R, ...)`` leaf and
    the rows are read from ``pool[layer]`` in the one gather."""
    safe = jnp.where(row_map >= 0, row_map, 0)
    rows = pool[safe] if layer is None else pool[layer, safe]
    valid = (row_map >= 0).reshape(row_map.shape + (1,) * (rows.ndim - 2))
    return jnp.where(valid, rows, jnp.zeros((), pool.dtype))


# A layer's K or V pool of at most this many bytes is read into a buffer of
# its own before the row gather: the v5e places such a buffer in its 128
# MiB of on-chip memory (a layer's 40 and 80 MiB were placed there, 120 MiB
# was not), and the gather from there runs about twice as fast as one from
# HBM.  A larger pool is gathered straight from the stacked leaf: staged in
# HBM, the copy costs more than it saves (PERF.md §6).
STAGE_POOL_BYTES = 96 << 20


@jax.named_scope("attention")
def attention(params: Params, cfg: ModelConfig, x: jax.Array, *,
              positions: jax.Array, tp: int, impl: str,
              window: int = 0, cache: Params | None = None,
              layer: jax.Array | None = None,
              cache_pos: jax.Array | None = None,
              row_map: jax.Array | None = None):
    """Returns (out, new_cache).  cache = {'k','v'}, stacked over the layers
    and updated in place at index ``layer``: (n, B, S_max, *row) — or, when
    ``row_map`` is given and the leaves are 4-D, a paged physical pool
    (n, R, *row) indexed through the (B, L) page table (DESIGN.md §12); a
    K/V row is :func:`kv_row_shape`."""
    pd = cfg.padded(tp)
    h, kv, hd = pd.n_heads, pd.n_kv_heads, cfg.head_dim
    rep = max(1, kv // max(1, cfg.n_kv_heads))  # kv replication factor

    q = _split_heads(x @ params["wq"], h, hd)
    k = _split_heads(x @ params["wk"], kv, hd)
    v = _split_heads(x @ params["wv"], kv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)

    if cache is not None:
        # decode: insert current k/v, attend over the prefix.  Sliding-window
        # layers may carry a ring buffer of `window` slots (slot = pos % W);
        # absolute slot positions reconstruct the mask (§Perf, gemma2 decode).
        # ``cache_pos`` is per-slot — a (B,) vector of absolute write
        # positions (a scalar broadcasts) — so co-scheduled requests at
        # different depths each write and mask at their own position
        # (DESIGN.md §11).  A (layers, R, ...) leaf is a paged pool: writes
        # and reads route through ``row_map``; ring leaves stay dense (a
        # ring is already O(window) per slot), so one model can mix both.
        # Every leaf holds all the layers: the new rows are scattered into
        # the stacked leaf itself, and the layer's rows read from it, so no
        # step writes a layer's cache back into the stack.
        paged = row_map is not None and cache["k"].ndim == 4
        cache_len = row_map.shape[1] if paged else cache["k"].shape[2]
        ring = not paged and window > 0 and cache_len == window
        bsz, sq = q.shape[0], q.shape[1]
        cpos = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32), (bsz,))
        b_idx = jnp.arange(bsz)[:, None]

        @jax.named_scope("kv_write")
        def put(name, val):
            leaf = cache[name]
            s = val.shape[1]
            if ring and s >= window:
                val = val[:, s - window:]   # a full wrap keeps only the tail
            kept = val.shape[1]
            # each row as the leaf lays it out (kv_row_shape)
            val = val.reshape((bsz, kept) + leaf.shape[2 if paged else 3:]
                              ).astype(leaf.dtype)
            rows = cpos[:, None] + (s - kept) + jnp.arange(kept)
            if paged:
                # logical row -> physical pool row via the slot's page
                # table.  Rows past the table (a parked slot) and unmapped
                # (-1) entries redirect to index R: negative indices WRAP
                # under mode="drop" (only >= size is out of bounds), so -1
                # would silently stomp the last pool row
                safe = jnp.clip(rows, 0, cache_len - 1)
                prow = jnp.take_along_axis(row_map, safe, axis=1)
                prow = jnp.where((rows < cache_len) & (prow >= 0), prow,
                                 leaf.shape[1])
                return leaf.at[layer, prow].set(val, mode="drop")
            if ring:
                rows = rows % window
            # out-of-range rows (a retired slot parked past its budget) are
            # dropped rather than clamped onto the last row
            return leaf.at[layer, b_idx, rows].set(val, mode="drop")

        def full(name, like):
            """Dense (B, L, ...) view of the layer's updated cache, its rows
            shaped as ``like``'s (a pool row may group heads: see
            :func:`kv_row_shape`)."""
            leaf = new_cache[name]
            if not paged:
                rows = leaf[layer]
            elif (math.prod(leaf.shape[1:]) * leaf.dtype.itemsize
                  <= STAGE_POOL_BYTES):
                # the layer's pool, read whole into a buffer of its own (the
                # barrier keeps XLA from fusing the slice into the gather);
                # the buffer is only read, nothing is written back
                rows = gather_pages(
                    jax.lax.optimization_barrier(leaf[layer]), row_map)
            else:
                rows = gather_pages(leaf, row_map, layer)
            return rows.reshape(rows.shape[:2] + like.shape[2:])

        if "k_scale" in cache:   # int8 KV: per-(token, head) scales
            def quant(z):
                sc = jnp.max(jnp.abs(z.astype(jnp.float32)), axis=-1,
                             keepdims=True) / 127.0 + 1e-12
                return jnp.round(z.astype(jnp.float32) / sc
                                 ).astype(jnp.int8), sc[..., 0]
            kq, ks = quant(k)
            vq, vs = quant(v)
            new_cache = {"k": put("k", kq), "v": put("v", vq),
                         "k_scale": put("k_scale", ks),
                         "v_scale": put("v_scale", vs)}
            ck = (full("k", kq).astype(jnp.float32)
                  * full("k_scale", ks)[..., None])
            cv = (full("v", vq).astype(jnp.float32)
                  * full("v_scale", vs)[..., None])
        else:
            new_cache = {"k": put("k", k), "v": put("v", v)}
            ck, cv = full("k", k), full("v", v)

        last = cpos + sq - 1                                 # (B,)
        if ring:
            slots = jnp.arange(cache_len)
            kpos = last[:, None] - jax.lax.rem(
                (last[:, None] - slots) % window + window, jnp.int32(window))
            out = _decode_attention(q, ck, cv, cfg, last, 0, kpos=kpos)
        else:
            out = _decode_attention(q, ck, cv, cfg, last, window)
    else:
        out = ops.attention(q, k, v, causal=cfg.causal,
                            softcap=cfg.attn_softcap, window=window,
                            implementation=impl)
        new_cache = None

    out = out.reshape(x.shape[0], x.shape[1], h * hd)
    return out @ params["wo"], new_cache


def _decode_attention(q, ck, cv, cfg: ModelConfig, last_pos, window: int,
                      kpos: jax.Array | None = None):
    """Single/few-token query against a (partially filled) cache.  Memory
    bound — the XLA einsum path with explicit position masking is the right
    tool; positions beyond ``last_pos`` are masked.  ``last_pos`` is per-slot
    ((B,) — a scalar broadcasts) so every sequence in the batch masks at its
    own absolute depth; ``kpos`` ((B, S_kv)) overrides slot positions
    (ring-buffer caches)."""
    b, sq, h, hd = q.shape
    skv, kvh = ck.shape[1], ck.shape[2]
    g = h // kvh
    qf = q.astype(jnp.float32).reshape(b, sq, kvh, g, hd)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qf, ck.astype(jnp.float32))
    logits *= hd ** -0.5
    logits = softcap(logits, cfg.attn_softcap)
    last_pos = jnp.broadcast_to(jnp.asarray(last_pos, jnp.int32), (b,))
    kpos = (jnp.broadcast_to(jnp.arange(skv), (b, skv)) if kpos is None
            else jnp.broadcast_to(kpos, (b, skv)))[:, None, :]  # (B, 1, Skv)
    qpos = (last_pos[:, None] - (sq - 1) + jnp.arange(sq))[..., None]
    mask = (kpos <= qpos) & (kpos >= 0)                   # (B, Sq, Skv)
    if window > 0:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[:, None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, cv.astype(jnp.float32))
    return out.reshape(b, sq, h, hd).astype(q.dtype)


def init_kv_cache(cfg: ModelConfig, batch: int, max_seq: int, tp: int,
                  dtype=jnp.bfloat16) -> Params:
    """Dense per-slot KV: ``(batch, max_seq)`` rows, each K and V row shaped
    by :func:`kv_row_shape`, each scale row one value a kv head."""
    shape = (batch, max_seq) + kv_row_shape(cfg, tp)
    if cfg.kv_int8:
        kv = cfg.padded(tp).n_kv_heads
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros((batch, max_seq, kv), jnp.float32),
                "v_scale": jnp.zeros((batch, max_seq, kv), jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def kv_row_shape(cfg: ModelConfig, tp: int) -> tuple[int, int]:
    """One row of a K or V cache leaf, pooled or dense: ``(KV / g, g * hd)``,
    ``g`` kv heads side by side, as many as fill the 128 lanes of a TPU
    vector register while every tensor-parallel shard keeps whole groups.
    A row ``(KV, 64)`` is narrower than the lanes: the TPU then lays the
    pool out with its rows minor, and the decode's row gather and scatter
    need a whole-pool relayout every step.  ``(KV / 2, 128)`` keeps the
    rows major and the pool compact; heads of 128 or more stay as they
    are."""
    kv, hd = cfg.padded(tp).n_kv_heads, cfg.head_dim
    g = math.gcd(128 // hd, kv // tp) if 128 % hd == 0 else 1
    return kv // g, g * hd


def init_paged_kv_pool(cfg: ModelConfig, rows: int, tp: int,
                       dtype=jnp.bfloat16) -> Params:
    """Physical KV pool of ``rows`` page-resident rows shared by every slot
    (DESIGN.md §12).  Same leaf set as :func:`init_kv_cache` minus the slot
    axis: the engine's page table supplies the slot -> row indirection.
    K and V rows are shaped by :func:`kv_row_shape`; the scales keep one
    value a kv head."""
    shape = (rows,) + kv_row_shape(cfg, tp)
    if cfg.kv_int8:
        kv = cfg.padded(tp).n_kv_heads
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros((rows, kv), jnp.float32),
                "v_scale": jnp.zeros((rows, kv), jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def kv_cache_specs(cfg: ModelConfig | None = None) -> Params:
    base = {"k": P(BATCH_AXES, None, TP, None),
            "v": P(BATCH_AXES, None, TP, None)}
    if cfg is not None and cfg.kv_int8:
        base["k_scale"] = P(BATCH_AXES, None, TP)
        base["v_scale"] = P(BATCH_AXES, None, TP)
    return base


def paged_kv_pool_specs(cfg: ModelConfig | None = None) -> Params:
    """Shardings for :func:`init_paged_kv_pool` leaves (rows, head groups,
    lanes): kv-heads shard over TP like the dense cache, whole groups to a
    shard; the physical-row axis stays replicated — rows are addressed by
    the host-side page table, which must see every row on every shard."""
    base = {"k": P(None, TP, None), "v": P(None, TP, None)}
    if cfg is not None and cfg.kv_int8:
        base["k_scale"] = P(None, TP)
        base["v_scale"] = P(None, TP)
    return base


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------

def mlp_init(key, cfg: ModelConfig, dtype) -> Params:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"w_up": _normal(ks[1], (d, f), d ** -0.5, dtype),
         "w_down": _normal(ks[2], (f, d), f ** -0.5, dtype)}
    p["w_gate"] = _normal(ks[0], (d, f), d ** -0.5, dtype)
    return p


def mlp_specs() -> Params:
    return {"w_gate": P(FSDP, TP), "w_up": P(FSDP, TP),
            "w_down": P(TP, FSDP)}


@jax.named_scope("mlp")
def mlp(params: Params, x: jax.Array, gelu: bool = False) -> jax.Array:
    act = jax.nn.gelu if gelu else jax.nn.silu
    h = act(x @ params["w_gate"]) * (x @ params["w_up"])
    return h @ params["w_down"]


# ---------------------------------------------------------------------------
# Mixture of Experts (top-k router, capacity dispatch, expert parallelism)
# ---------------------------------------------------------------------------

def moe_init(key, cfg: ModelConfig, tp: int, dtype) -> Params:
    pd = cfg.padded(tp)
    e, d, f = pd.n_experts, cfg.d_model, cfg.moe_d_ff
    ks = jax.random.split(key, 4)
    # padded experts are routed -inf -> never selected (exact); strong f32
    # so the aval matches a checkpoint round-trip (no weak-type cache split)
    mask = jnp.where(jnp.arange(e) < cfg.n_experts, 0.0, -1e30) \
        .astype(jnp.float32)
    return {
        "router": _normal(ks[0], (d, e), d ** -0.5, jnp.float32),
        "router_mask": mask,
        "w_gate": _normal(ks[1], (e, d, f), d ** -0.5, dtype),
        "w_up": _normal(ks[2], (e, d, f), d ** -0.5, dtype),
        "w_down": _normal(ks[3], (e, f, d), f ** -0.5, dtype),
    }


def moe_specs() -> Params:
    return {"router": P(None, TP), "router_mask": P(TP),
            "w_gate": P(TP, FSDP, None), "w_up": P(TP, FSDP, None),
            "w_down": P(TP, None, FSDP)}


def _dispatch_group(x2, logits, k, cap):
    """Group-local top-k routing + capacity scatter.  x2: (T, d);
    logits: (T, E).  Returns (buf (E, cap, d), flat_e, slot, keep, gates)."""
    t, d = x2.shape
    e = logits.shape[-1]
    top_vals, top_idx = jax.lax.top_k(logits, k)              # (T, K)
    gates = jax.nn.softmax(top_vals, axis=-1).astype(x2.dtype)
    flat_e = top_idx.reshape(-1)                              # (T*K,) token-major
    # position-within-expert via stable argsort ranking: O(n log n), versus
    # the classic (T·K, E) one-hot cumsum that XLA lowers quadratically
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    start = jnp.searchsorted(sorted_e, jnp.arange(e))         # first slot per e
    pos_sorted = jnp.arange(t * k) - start[sorted_e]
    mypos = jnp.zeros((t * k,), jnp.int32).at[order].set(pos_sorted)
    keep = (mypos < cap)[:, None].astype(x2.dtype)
    slot = jnp.minimum(mypos, cap - 1)
    xrep = jnp.broadcast_to(x2[:, None, :], (t, k, d)).reshape(t * k, d)
    buf = jnp.zeros((e, cap, d), x2.dtype).at[flat_e, slot].add(xrep * keep)
    return buf, flat_e, slot, keep, gates


@jax.named_scope("moe")
def moe(params: Params, cfg: ModelConfig, x: jax.Array, tp: int) -> jax.Array:
    """Grouped capacity-dispatch MoE (DESIGN.md §5, EXPERIMENTS.md §Perf).

    Routing, ranking and the capacity scatter run *per batch-group* (vmap
    over the batch dim, which is data-sharded) so every token-indexed op
    stays shard-local; only the expert einsums communicate (buf grouped over
    'data' × experts over 'model').  The original global-token scatter made
    GSPMD replicate the dispatch — 23 TB/device of wire on granite train_4k;
    grouping removes ~all of it.  FLOPs still scale with top_k·T (capacity
    1.25×), not E·T.
    """
    b, s, d = x.shape
    e = params["w_gate"].shape[0]
    k = cfg.top_k
    cap = max(8, int(math.ceil(s * k / e * cfg.capacity_factor)))

    logits = (jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                         params["router"]) + params["router_mask"])
    buf, flat_e, slot, keep, gates = jax.vmap(
        functools.partial(_dispatch_group, k=k, cap=cap))(
        x.reshape(b, s, d), logits)                          # buf: (B,E,cap,d)

    from repro.distributed.context import constrain
    gspec = P(("pod", FSDP), TP, None, None)                 # groups x experts
    if cfg.moe_int8_dispatch:
        # quantize the dispatch buffer so the group->expert resharding moves
        # int8 (halves the dominant MoE collectives; EXPERIMENTS.md §Perf)
        scale = jnp.max(jnp.abs(buf.astype(jnp.float32)),
                        axis=-1, keepdims=True) / 127.0 + 1e-12
        q = jnp.round(buf.astype(jnp.float32) / scale).astype(jnp.int8)
        q = constrain(q, gspec)
        scale = constrain(scale.astype(jnp.float32), gspec)
        buf = (q.astype(jnp.float32) * scale).astype(x.dtype)
    buf = constrain(buf, gspec)
    h = (jax.nn.silu(jnp.einsum("becd,edf->becf", buf, params["w_gate"]))
         * jnp.einsum("becd,edf->becf", buf, params["w_up"]))
    h = constrain(h, P(("pod", FSDP), TP, None, None))
    out_buf = jnp.einsum("becf,efd->becd", h, params["w_down"])
    # re-shard group-local (full E per group) BEFORE the token gather: the
    # gather indexes the expert dim, which would otherwise all-reduce a full
    # activation per layer (EXPERIMENTS.md §Perf iteration 2)
    out_buf = constrain(out_buf, P(("pod", FSDP), None, None, None))

    def gather_group(out_b, flat_e_b, slot_b, keep_b, gates_b):
        out_tok = out_b[flat_e_b, slot_b] * keep_b           # (S*K, d)
        return (out_tok.reshape(s, k, d) * gates_b[..., None]).sum(axis=1)

    out = jax.vmap(gather_group)(out_buf, flat_e, slot, keep, gates)
    return out.astype(x.dtype)
