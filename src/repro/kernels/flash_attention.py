"""Flash attention Pallas kernel (fwd) with causal + sliding-window masks
and an always-visible prefix (hymba meta tokens).

TPU-shaped: grid = (B, H, T/block_q, S/block_k) with the K dimension
innermost (sequential), carrying the online-softmax state (m, l, acc) in
VMEM scratch across K steps -- the standard TPU adaptation of the GPU
flash algorithm (no warp-level primitives; the MXU consumes whole
[block_q, block_k] tiles and the VPU does the rescaling).

(block_q, block_k) are the paper-sense "block size" tuned by
repro.core.kerneltune: VMEM use = double-buffered q, k, v and o tiles +
the fp32 block_q*block_k scores and accumulators.

The backward pass recomputes through the jnp reference (custom_vjp): on
real TPU one would add the flash bwd kernel; correctness and the training
path are preserved either way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import flash_attention_ref

_NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, block_q, block_k, seq_q, seq_k, window, n_meta, causal):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * block_q + (seq_k - seq_q)          # right-aligned
    k_start = ik * block_k

    # block-level skip: entirely-masked tiles cost nothing
    run = jnp.bool_(True)
    if causal:
        run &= k_start <= q_start + block_q - 1
    if window > 0:
        alive = (q_start - (k_start + block_k - 1)) < window
        run &= alive | (k_start < n_meta)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)            # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)            # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones_like(s, bool)
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= ((qpos - kpos) < window) | (kpos < n_meta)
        s = jnp.where(mask, s, _NEG)

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_cur[:, None])
        alpha = jnp.exp(m_prev - m_cur)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_cur

    @pl.when(ik == pl.num_programs(3) - 1)
    def _flush():
        den = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / den).astype(o_ref.dtype)


def _fwd(q, k, v, *, scale, window, n_meta, causal, block_q, block_k,
         interpret):
    b, t, h, d = q.shape
    s = k.shape[1]
    kvh = k.shape[2]
    g = h // kvh
    assert t % block_q == 0 and s % block_k == 0, (t, s, block_q, block_k)
    # layout: [B, H, T, d] blocks of (1, 1, block, d)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    grid = (b, h, t // block_q, s // block_k)
    kernel = functools.partial(
        _kernel, scale=scale, block_q=block_q, block_k=block_k,
        seq_q=t, seq_k=s, window=window, n_meta=n_meta, causal=causal)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bb, hh, qq, kk: (bb, hh, qq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bb, hh, qq, kk, g=g: (bb, hh // g, kk, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bb, hh, qq, kk, g=g: (bb, hh // g, kk, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bb, hh, qq, kk: (bb, hh, qq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),       # running max
            pltpu.VMEM((block_q,), jnp.float32),       # running denom
            pltpu.VMEM((block_q, d), jnp.float32),     # output accum
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)                   # back to [B,T,H,d]


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention(q, k, v, scale, window, n_meta, causal, block_q, block_k,
                    interpret):
    return _fwd(q, k, v, scale=scale, window=window, n_meta=n_meta,
                causal=causal, block_q=block_q, block_k=block_k,
                interpret=interpret)


def _ref_expand(q, k, v, scale, window, n_meta, causal):
    g = q.shape[2] // k.shape[2]
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    return flash_attention_ref(q, k, v, window=window, n_meta=n_meta,
                               scale=scale, causal=causal)


def _vjp_fwd(q, k, v, scale, window, n_meta, causal, block_q, block_k,
             interpret):
    out = _fwd(q, k, v, scale=scale, window=window, n_meta=n_meta,
               causal=causal, block_q=block_q, block_k=block_k,
               interpret=interpret)
    return out, (q, k, v)


def _vjp_bwd(scale, window, n_meta, causal, block_q, block_k, interpret,
             res, g_out):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda qq, kk, vv: _ref_expand(qq, kk, vv, scale, window, n_meta,
                                       causal), q, k, v)
    return vjp(g_out)


flash_attention.defvjp(_vjp_fwd, _vjp_bwd)


def vmem_bytes(block_q: int, block_k: int, d: int, dtype_bytes: int = 2):
    """VMEM working set of one grid step: the q, k, v input and o output
    tiles double-buffered by the pipeline, plus fp32 scores, running
    max/denominator and output accumulator."""
    return 2 * (2 * block_q * d + 2 * block_k * d) * dtype_bytes \
        + (block_q * block_k + block_q * d + 2 * block_q) * 4
