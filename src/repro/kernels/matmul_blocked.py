"""Blocked matmul Pallas kernel with explicit BlockSpec VMEM tiling.

The (block_m, block_n, block_k) tile triple is the kernel-level "block
size" in the paper's sense: it fixes the VMEM working set (double-buffered
bm*bk, bk*bn input and bm*bn output tiles plus the bm*bn fp32 accumulator)
and the MXU utilization, and is tuned by repro.core.kerneltune the same way
the paper tunes (p_r, p_c).

Grid = (M/bm, N/bn, K/bk), K innermost (sequential on TPU), accumulating in
an fp32 VMEM scratch tile that is written out on the last K step.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...].astype(jnp.float32),
                            b_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul_blocked(a: jax.Array, b: jax.Array, *, block_m: int = 128,
                   block_n: int = 128, block_k: int = 128,
                   interpret: bool = False) -> jax.Array:
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        f"shapes ({m},{k})x({k},{n}) not divisible by blocks "
        f"({block_m},{block_n},{block_k}); pad via ops.matmul")
    grid = (m // block_m, n // block_n, k // block_k)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(a, b)


def vmem_bytes(block_m: int, block_n: int, block_k: int,
               dtype_bytes: int = 2) -> int:
    """VMEM working set of one grid step -- the kernel tuner's OOM check.
    The pipeline double-buffers both input tiles and the output tile (the
    next block loads while this one computes); the fp32 accumulator is
    single scratch."""
    return 2 * (block_m * block_k + block_k * block_n
                + block_m * block_n) * dtype_bytes + block_m * block_n * 4
