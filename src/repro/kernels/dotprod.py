"""Dot-product Pallas kernel — the DOT hardware intrinsic.

The most general (and least data-reusing) intrinsic of the paper's four:
streams both operands once, accumulates a scalar.  bk is ``pe_depth``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dot_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    acc_ref[...] += jnp.sum(a * b).reshape(1, 1)

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def dot(a: jax.Array, b: jax.Array, *, bk: int = 2048,
        interpret: bool = False) -> jax.Array:
    """sum(a * b) over 1-D operands; returns shape (1, 1) f32."""
    (k,) = a.shape
    assert b.shape == (k,)
    bk = min(bk, k)
    kp = pl.cdiv(k, bk) * bk
    a = jnp.pad(a, (0, kp - k))
    b = jnp.pad(b, (0, kp - k))
    grid = (kp // bk,)
    return pl.pallas_call(
        functools.partial(_dot_kernel, n_k=grid[0]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bk), lambda kk: (0, kk)),
            pl.BlockSpec((1, bk), lambda kk: (0, kk)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda kk: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(a[None, :], b[None, :])
