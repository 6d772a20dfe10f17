"""Blocked GEMM Pallas kernel — the GEMM hardware intrinsic (paper §II-B).

The block shape (bm, bn, bk) *is* the co-designed accelerator parameter set:
``pe_rows × pe_cols`` maps to (bm, bn) and ``pe_depth`` to bk (DESIGN.md §2).
Grid = (M/bm, N/bn, K/bk) with the contraction innermost ("arbitrary") so the
f32 VMEM accumulator is revisited; (bm, bk)/(bk, bn) tiles are the scratchpad
residents that HASCO's VMEM-legality constraint sizes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def gemm(a: jax.Array, b: jax.Array, *, bm: int = 256, bn: int = 256,
         bk: int = 512, interpret: bool = False) -> jax.Array:
    """C = A @ B with f32 accumulation.  A: (M, K), B: (K, N)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    # zero-pad to block multiples: zeros are exact for the accumulation
    mp, np_, kp = (pl.cdiv(m, bm) * bm, pl.cdiv(n, bn) * bn,
                   pl.cdiv(k, bk) * bk)
    a = jnp.pad(a, ((0, mp - m), (0, kp - k)))
    b = jnp.pad(b, ((0, kp - k), (0, np_ - n)))
    grid = (mp // bm, np_ // bn, kp // bk)
    out = pl.pallas_call(
        functools.partial(_gemm_kernel, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
    return out[:m, :n]
