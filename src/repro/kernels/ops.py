"""jit'd public wrappers for the Pallas kernels.

Handle padding to block multiples, dtype plumbing, and backend selection
(compiled on a TPU; ``interpret=True`` on the CPU so the kernel bodies
execute -- and are tested -- there; any other backend is refused).  Block
sizes default to MXU-aligned values and may be overridden
by the kernel autotuner (repro.core.kerneltune).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import matmul_blocked as _mm


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas kernels run compiled on a TPU or interpreted on the "
            f"CPU; the default backend is {backend!r}")
    return backend == "cpu"


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k"))
def matmul(a: jax.Array, b: jax.Array, *, block_m: int = 128,
           block_n: int = 128, block_k: int = 128) -> jax.Array:
    m, k = a.shape
    _, n = b.shape
    bm, bn, bk = (min(block_m, m), min(block_n, n), min(block_k, k))
    a, _ = _pad_to(a, bm, 0)
    a, _ = _pad_to(a, bk, 1)
    b, _ = _pad_to(b, bk, 0)
    b, _ = _pad_to(b, bn, 1)
    out = _mm.matmul_blocked(a, b, block_m=bm, block_n=bn, block_k=bk,
                             interpret=_interpret())
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=(
    "window", "n_meta", "scale", "causal", "block_q", "block_k"))
def flash_attention(q, k, v, *, window: int = 0, n_meta: int = 0,
                    scale: float | None = None, causal: bool = True,
                    block_q: int = 128, block_k: int = 128):
    """q: [B,T,H,dh]; k,v: [B,S,KV,dh] with KV | H (GQA via index_map)."""
    b, t, h, dh = q.shape
    s = k.shape[1]
    scale = dh ** -0.5 if scale is None else float(scale)
    bq, bk_ = min(block_q, t), min(block_k, s)
    q, pad_q = _pad_to(q, bq, 1)
    k, pad_k = _pad_to(k, bk_, 1)
    v, _ = _pad_to(v, bk_, 1)
    if pad_k:
        # padded keys must never win the softmax: rely on causal mask
        # (padded positions sit in the future of every real query)
        assert causal, "non-causal padding needs an explicit length mask"
    out = _fa.flash_attention(q, k, v, scale, window, n_meta, causal,
                              bq, bk_, _interpret())
    return out[:, :t]
