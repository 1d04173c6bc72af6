"""The benchmark's weights, made from ``--seed`` on the device in one jitted
call.  Each leaf's values depend only on the seed and the leaf's name, not
on how it is sharded, so the program and the reference get the same
weights under their own layouts."""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A key for any whole number: the low 32 bits seed it, the rest are
    folded in (``jax.random.key`` keeps only 32 bits)."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make(specs: dict, seed: int, shardings: dict) -> dict:
    """``specs``: name -> (shape, dtype, std); std 0 gives zeros.  Normal
    values are drawn in float32, scaled, and rounded to ``dtype``."""
    if not jax.config.jax_threefry_partitionable:
        raise RuntimeError("weights need jax_threefry_partitionable: values "
                           "must not depend on the sharding")
    names = sorted(specs)

    def gen(key):
        out = {}
        for n in names:
            shape, dtype, std = specs[n]
            if std == 0:
                out[n] = jnp.zeros(shape, dtype)
            else:
                k = jax.random.fold_in(key, zlib.crc32(n.encode()) & 0x7FFFFFFF)
                out[n] = (jax.random.normal(k, shape, jnp.float32)
                          * std).astype(dtype)
        return out

    return jax.jit(gen, out_shardings={n: shardings[n] for n in names})(
        seed_key(seed))
