"""Random weights from the seed, made by the benchmark on the device.

One jitted call builds the whole tree that the family's ``tree(cfg, key)``
describes, in the dtype it is served in.  On a mesh the call's outputs are
the program's parameter shardings (``repro.distributed.sharding``), so each
chip builds only its own part and no device ever holds the whole tree.  The
random bits do not depend on the sharding: a sharded tree holds, bit for
bit, the values of the unsharded one for the same seed.  The benchmark makes
the weights so that the reference never takes anything the program made.
"""
from __future__ import annotations

import functools

import jax


def make(family, cfg, seed: int, mesh=None):
    """The weights of ``cfg`` for ``seed``: on the default device, or laid
    out over ``mesh`` as the program shards its parameters."""
    key = jax.random.key(seed % 2**32 ^ (seed >> 32))
    build = functools.partial(family.tree, cfg)
    if mesh is None:
        return jax.jit(build)(key)
    from repro.distributed.sharding import param_shardings
    from repro.models import family_module

    return jax.jit(build, out_shardings=param_shardings(
        family_module(cfg), cfg, mesh))(key)


def shapes(family, cfg):
    """The weights' shapes and dtypes, with nothing allocated."""
    return jax.eval_shape(functools.partial(family.tree, cfg),
                          jax.random.key(0))
