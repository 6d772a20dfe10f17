"""Blocked GEMV Pallas kernel — the GEMV hardware intrinsic.

y = A @ x.  The vector is broadcast as a (1, bk) block; rows stream in
(bm, bk) tiles (pe_rows × pe_depth in HASCO terms).  Accumulation in a
(bm, 1)-shaped f32 VMEM scratch — GEMV on the MXU is rank-deficient, which is
exactly why the paper's Fig. 7 shows dedicated intrinsics winning; the cost
model carries the same penalty.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gemv_kernel(a_ref, x_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.float32)          # (bm, bk)
    x = x_ref[...].astype(jnp.float32)          # (1, bk)
    acc_ref[...] += jnp.sum(a * x, axis=1, keepdims=True)

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "interpret"))
def gemv(a: jax.Array, x: jax.Array, *, bm: int = 512, bk: int = 512,
         interpret: bool = False) -> jax.Array:
    """y[m] = sum_k A[m,k] x[k].  Returns shape (M,)."""
    m, k = a.shape
    assert x.shape == (k,)
    bm, bk = min(bm, m), min(bk, k)
    mp, kp = pl.cdiv(m, bm) * bm, pl.cdiv(k, bk) * bk
    a = jnp.pad(a, ((0, mp - m), (0, kp - k)))
    x = jnp.pad(x, (0, kp - k))
    grid = (mp // bm, kp // bk)
    out = pl.pallas_call(
        functools.partial(_gemv_kernel, n_k=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, kk: (i, kk)),
            pl.BlockSpec((1, bk), lambda i, kk: (0, kk)),
        ],
        out_specs=pl.BlockSpec((bm, 1), lambda i, kk: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, 1), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(a, x[None, :])
    return out[:m, 0]
