"""Chunked RWKV-6 (Finch) WKV Pallas kernel.

TPU adaptation (DESIGN.md §Arch-applicability): the data-dependent per-channel
decay recurrence is *not* a fixed-shape intrinsic — HASCO's matcher cannot
tensorize it directly.  We therefore chunk the sequence: within-chunk terms
become dense (MXU-friendly) contractions and the recurrence survives only at
chunk granularity, carried in a VMEM-resident f32 state.  All exponentials
are differences of log-decay cumsums with non-positive exponents → stable.

Per chunk of length L (lw = inclusive cumsum of log-decay, aq = exclusive):
  o_t     = Σ_d r_td e^{aq_td} S0[d]  +  Σ_{s<t} Σ_d r_td k_sd e^{aq_td−lw_sd} v_s
            + (Σ_d r_td u_d k_td) v_t
  S_new[d] = e^{lw_Ld} S0[d] + Σ_s k_sd e^{lw_Ld−lw_sd} v_s
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rwkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
                  o_ref, sT_ref, state_ref, *, chunk: int, n_t: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        state_ref[...] = s0_ref[0].astype(jnp.float32)

    r = r_ref[0].astype(jnp.float32)               # (L, Dk)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)               # (L, Dv)
    w = w_ref[0].astype(jnp.float32)               # (L, Dk) log-decay <= 0
    u = u_ref[0].astype(jnp.float32)               # (1, Dk)

    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive prefix sum over time as a lower-triangular-ones matmul: the
    # MXU form of cumsum, which has no Mosaic lowering
    tril = (si <= ti).astype(jnp.float32)
    lw = jnp.dot(tril, w, precision=jax.lax.Precision.HIGHEST,
                 preferred_element_type=jnp.float32)
    aq = lw - w                                    # exclusive
    s0 = state_ref[...]                            # (Dk, Dv)

    # inter-chunk: query against the carried state
    o = jnp.dot(r * jnp.exp(aq), s0, preferred_element_type=jnp.float32)

    # intra-chunk, one source position s at a time (Mosaic lays out no
    # (L, L, Dk) pairwise tensor): exponents aq_t - lw_s <= 0 where s < t
    t_col = ti[:, :1]                              # (L, 1) query position
    for src in range(chunk - 1):
        pair = jnp.exp(jnp.minimum(aq - lw[src:src + 1], 0.0))   # (L, Dk)
        score = jnp.sum(r * k[src:src + 1] * pair, axis=1, keepdims=True)
        o += jnp.where(t_col > src, score, 0.0) * v[src:src + 1]

    # current-token bonus (diag(u))
    o += jnp.sum(r * u * k, axis=-1, keepdims=True) * v

    # state update
    lw_L = lw[-1:, :]                              # (1, Dk)
    kd = k * jnp.exp(lw_L - lw)                    # <= k, stable
    state_ref[...] = jnp.exp(lw_L.T) * s0 + jnp.dot(
        kd.T, v, preferred_element_type=jnp.float32)

    o_ref[0] = o.astype(o_ref.dtype)

    @pl.when(t == n_t - 1)
    def _flush():
        sT_ref[0] = state_ref[...].astype(sT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
          u: jax.Array, state: jax.Array | None = None, *,
          chunk: int = 16, interpret: bool = False
          ) -> tuple[jax.Array, jax.Array]:
    """r/k/w: (B, T, H, Dk); v: (B, T, H, Dv); u: (H, Dk);
    state: (B, H, Dk, Dv) or None.  Returns (out (B,T,H,Dv), final state)."""
    b, t, h, dk = k.shape
    dv = v.shape[-1]
    assert t % chunk == 0, f"T={t} must be a multiple of chunk={chunk}"
    if state is None:
        state = jnp.zeros((b, h, dk, dv), jnp.float32)

    def flat(x):
        return jnp.moveaxis(x, 2, 1).reshape(b * h, t, x.shape[-1])

    rf, kf, vf, wf = flat(r), flat(k), flat(v), flat(w)
    uf = jnp.broadcast_to(u[None], (b, h, dk)).reshape(b * h, 1, dk)
    s0 = state.reshape(b * h, dk, dv)

    n_t = t // chunk
    grid = (b * h, n_t)
    o, sT = pl.pallas_call(
        functools.partial(_rwkv6_kernel, chunk=chunk, n_t=n_t),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda bh, tt: (bh, tt, 0)),
            pl.BlockSpec((1, chunk, dk), lambda bh, tt: (bh, tt, 0)),
            pl.BlockSpec((1, chunk, dv), lambda bh, tt: (bh, tt, 0)),
            pl.BlockSpec((1, chunk, dk), lambda bh, tt: (bh, tt, 0)),
            pl.BlockSpec((1, 1, dk), lambda bh, tt: (bh, 0, 0)),
            pl.BlockSpec((1, dk, dv), lambda bh, tt: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dv), lambda bh, tt: (bh, tt, 0)),
            pl.BlockSpec((1, dk, dv), lambda bh, tt: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t, dv), v.dtype),
            jax.ShapeDtypeStruct((b * h, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rf, kf, vf, wf, uf, s0)

    out = jnp.moveaxis(o.reshape(b, h, t, dv), 1, 2)
    return out, sT.reshape(b, h, dk, dv)
