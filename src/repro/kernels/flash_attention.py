"""Flash attention (forward) Pallas kernel with GQA, causal masking,
gemma2 logit soft-capping and sliding-window (local) attention.

Online-softmax over kv blocks (the innermost, "arbitrary" grid dim); per
q-block scratch holds the running max/denominator and the f32 accumulator —
the canonical VMEM-resident working set.  The (bq, bkv) block shape is the
HASCO-tunable "PE array" of the attention intrinsic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, softcap: float, window: int,
                  bq: int, bkv: int, n_kv: int, q_offset: int, kv_len: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                 # (bq, d)
    k = k_ref[0].astype(jnp.float32)                 # (bkv, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)

    iq = pl.program_id(1)
    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0) \
        + q_offset
    kpos = ik * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    mask = kpos < kv_len                             # padded keys never attend
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                              # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v_ref[0].astype(jnp.float32),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _flush():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "softcap", "window", "scale", "bq", "bkv", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, softcap: float = 0.0,
                    window: int = 0, scale: float | None = None,
                    bq: int = 128, bkv: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Sq, H, D);  k, v: (B, Skv, Hkv, D);  GQA via H % Hkv == 0.

    Sequence lengths are padded to the block sizes internally; the causal
    offset aligns the last query with the last key (decode convention).
    """
    b, sq, h, d = q.shape
    _, skv, hkv, _ = k.shape
    assert h % hkv == 0
    g = h // hkv
    scale = float(scale if scale is not None else d ** -0.5)

    bq = min(bq, max(8, sq))
    bkv = min(bkv, skv)
    sq_p = pl.cdiv(sq, bq) * bq
    skv_p = pl.cdiv(skv, bkv) * bkv
    qp = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, skv_p - skv), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, skv_p - skv), (0, 0), (0, 0)))
    # padded keys must never win the max: push them outside the causal mask
    q_offset = skv - sq

    qf = jnp.moveaxis(qp, 2, 1).reshape(b * h, sq_p, d)
    kf = jnp.moveaxis(kp, 2, 1).reshape(b * hkv, skv_p, d)
    vf = jnp.moveaxis(vp, 2, 1).reshape(b * hkv, skv_p, d)

    n_kv = skv_p // bkv
    grid = (b * h, sq_p // bq, n_kv)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, softcap=float(softcap),
        window=int(window), bq=bq, bkv=bkv, n_kv=n_kv, q_offset=q_offset,
        kv_len=skv)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bkv, d),
                         lambda bh, iq, ik, g=g: (bh // g, ik, 0)),
            pl.BlockSpec((1, bkv, d),
                         lambda bh, iq, ik, g=g: (bh // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)

    out = out.reshape(b, h, sq_p, d)[:, :, :sq]
    return jnp.moveaxis(out, 1, 2)
