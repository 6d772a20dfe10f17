"""Chunked Mamba-2 SSD Pallas kernel.

Scalar-per-head decay makes the chunked form pure MXU work (unlike RWKV-6's
per-channel decay): the (L, L) intra-chunk decay mask multiplies a C·Bᵀ
Gram matrix.  All exponents are non-positive — numerically stable.

Per chunk (la = inclusive cumsum of log-decay a):
  y_t    = (c_t e^{la_t})·h0 + Σ_{s≤t} e^{la_t−la_s} (c_t·b_s) x_s
  h_new  = e^{la_L} h0 + Σ_s e^{la_L−la_s} b_s x_sᵀ
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mamba2_kernel(x_ref, a_ref, b_ref, c_ref, h0_ref,
                   y_ref, hT_ref, state_ref, *, chunk: int, n_t: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        state_ref[...] = h0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)               # (L, P)
    a = a_ref[0].astype(jnp.float32)               # (1, L) log-decay <= 0
    b = b_ref[0].astype(jnp.float32)               # (L, N)
    c = c_ref[0].astype(jnp.float32)               # (L, N)

    # inclusive prefix sums over time as lower-triangular-ones matmuls (the
    # MXU form of cumsum, which has no Mosaic lowering), taken once as a
    # column and once as a row so that no vector transpose is needed
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tril = (si <= ti).astype(jnp.float32)
    nt = (((1,), (1,)), ((), ()))
    hi = jax.lax.Precision.HIGHEST
    la_col = jax.lax.dot_general(tril, a, nt, precision=hi,
                                 preferred_element_type=jnp.float32)  # (L, 1)
    la_row = jax.lax.dot_general(a, tril, nt, precision=hi,
                                 preferred_element_type=jnp.float32)  # (1, L)
    la_last = la_col[chunk - 1:, :]                # (1, 1)
    h0 = state_ref[...]                            # (N, P)

    # inter-chunk
    y = jnp.dot(c * jnp.exp(la_col), h0, preferred_element_type=jnp.float32)

    # intra-chunk (inclusive diagonal: y_t uses h_t after its own update)
    decay = jnp.where(si <= ti, jnp.exp(jnp.minimum(la_col - la_row, 0.0)),
                      0.0)
    gram = jax.lax.dot_general(c, b, nt,
                               preferred_element_type=jnp.float32) * decay
    y += jnp.dot(gram, x, preferred_element_type=jnp.float32)

    # state update
    bd = b * jnp.exp(la_last - la_col)
    state_ref[...] = jnp.exp(la_last) * h0 + jnp.dot(
        bd.T, x, preferred_element_type=jnp.float32)

    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(t == n_t - 1)
    def _flush():
        hT_ref[0] = state_ref[...].astype(hT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mamba2(x: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
           state: jax.Array | None = None, *, chunk: int = 64,
           interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """x: (B, T, H, P); a: (B, T, H) log-decay; b/c: (B, T, H, N);
    state: (B, H, N, P) or None.  Returns (y (B,T,H,P), final state)."""
    bs, t, h, p = x.shape
    n = b.shape[-1]
    assert t % chunk == 0, f"T={t} must be a multiple of chunk={chunk}"
    if state is None:
        state = jnp.zeros((bs, h, n, p), jnp.float32)

    def flat(z):
        return jnp.moveaxis(z, 2, 1).reshape(bs * h, t, z.shape[-1])

    n_t = t // chunk
    xf, bf, cf = flat(x), flat(b), flat(c)
    # one (1, chunk) row per (head, chunk): a block whose two minor dims are
    # the array's own obeys the TPU's (8, 128) block rule at any chunk size
    af = jnp.moveaxis(a, 2, 1).reshape(bs * h * n_t, 1, chunk)
    h0 = state.reshape(bs * h, n, p)

    grid = (bs * h, n_t)
    y, hT = pl.pallas_call(
        functools.partial(_mamba2_kernel, chunk=chunk, n_t=n_t),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda bh, tt: (bh, tt, 0)),
            pl.BlockSpec((1, 1, chunk),
                         lambda bh, tt: (bh * n_t + tt, 0, 0)),
            pl.BlockSpec((1, chunk, n), lambda bh, tt: (bh, tt, 0)),
            pl.BlockSpec((1, chunk, n), lambda bh, tt: (bh, tt, 0)),
            pl.BlockSpec((1, n, p), lambda bh, tt: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda bh, tt: (bh, tt, 0)),
            pl.BlockSpec((1, n, p), lambda bh, tt: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bs * h, t, p), x.dtype),
            jax.ShapeDtypeStruct((bs * h, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xf, af, bf, cf, h0)

    out = jnp.moveaxis(y.reshape(bs, h, t, p), 1, 2)
    return out, hT.reshape(bs, h, n, p)
