"""Direct 2-D convolution Pallas kernel — the CONV2D hardware intrinsic.

C[k,x,y] = sum_{c,r,s} A[c,x+r,y+s] * W[k,c,r,s]   ('valid').

TPU adaptation of the paper's dedicated conv accelerator: the input tile is
scratchpad(VMEM)-resident with its halo, filters stream per-k block, and the
R×S taps unroll into MXU matmuls of (C, X·W) windows of the row-flattened
input — a direct conv, *not* im2col (the paper's Fig. 11 shows why
materialized im2col loses).  Workloads
bigger than VMEM are decomposed by the software layer (the tensorize
interface) into sub-workloads that fit — exactly the paper's HW/SW split.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _conv_kernel(a_ref, w_ref, o_ref, acc_ref, *, width: int,
                 taps: tuple[tuple[int, int], ...]):
    # a_ref: (C, L) input rows laid end to end; output column x·width + y
    # reads input column x·width + y + r·width + s for tap (r, s), so every
    # tap is one contiguous (C, X·width) window — no in-kernel reshape
    n = acc_ref.shape[1]
    acc_ref[...] = jnp.zeros_like(acc_ref)
    for t, (r, s) in enumerate(taps):
        off = r * width + s
        acc_ref[...] += jnp.dot(w_ref[t], a_ref[:, off:off + n],
                                preferred_element_type=jnp.float32)
    o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def conv2d(a: jax.Array, w: jax.Array, *, bk: int = 128,
           interpret: bool = False) -> jax.Array:
    """a: (C, H, W);  w: (K, C, R, S);  returns (K, H-R+1, W-S+1)."""
    c, h, wd = a.shape
    k, c2, r, s = w.shape
    assert c == c2
    x, y = h - r + 1, wd - s + 1
    bk = min(bk, k)
    grid = (pl.cdiv(k, bk),)
    taps = tuple((i, j) for i in range(r) for j in range(s))
    # the last tap's window runs s - 1 columns past the input: zero-pad
    a_flat = jnp.pad(a.reshape(c, h * wd), ((0, 0), (0, s - 1)))
    w_taps = jnp.transpose(w, (2, 3, 0, 1)).reshape(r * s, k, c)
    n = x * wd            # whole rows; the wrapper drops the s - 1 wrap columns
    out = pl.pallas_call(
        functools.partial(_conv_kernel, width=wd, taps=taps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((c, h * wd + s - 1), lambda kk: (0, 0)),
            pl.BlockSpec((r * s, bk, c), lambda kk: (0, kk, 0)),
        ],
        out_specs=pl.BlockSpec((bk, n), lambda kk: (kk, 0)),
        out_shape=jax.ShapeDtypeStruct((k, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((bk, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(a_flat, w_taps)
    return out.reshape(k, x, wd)[:, :, :y]
