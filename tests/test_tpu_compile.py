"""Compile-only checks of the Pallas kernels for a TPU v5e that is described,
not attached: the chip's own compiler refuses what interpret mode accepts
(unaligned slices, tiles over the scoped-VMEM limit).  Nothing runs, so
these say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.configs.workloads import flash_case, gemm_cases
from repro.core.kerneltune import bucket_case, candidate_tiles, feasible_tiles
from repro.kernels import flash_attention as fa
from repro.kernels.matmul_blocked import matmul_blocked
from repro.kernels.timing import tile_vmem_bytes


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any reason the chip is unknown
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _pad(x, mult):
    return -(-x // mult) * mult


def compile_matmul(one_chip, m, k, n, tile, dtype=jnp.bfloat16):
    """Compiled text of the blocked matmul at (m x k)(k x n), padded to
    the tile as ``ops.matmul`` pads."""
    bm, bn, bk = tile
    a = jax.ShapeDtypeStruct((_pad(m, bm), _pad(k, bk)), dtype,
                             sharding=one_chip)
    b = jax.ShapeDtypeStruct((_pad(k, bk), _pad(n, bn)), dtype,
                             sharding=one_chip)
    f = jax.jit(lambda a, b: matmul_blocked(a, b, block_m=bm, block_n=bn,
                                            block_k=bk, interpret=False))
    return f.lower(a, b).compile().as_text()


def compile_flash(one_chip, t, heads, kv_heads, d, tile, window=0):
    q = jax.ShapeDtypeStruct((1, t, heads, d), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, t, kv_heads, d), jnp.bfloat16,
                              sharding=one_chip)
    f = jax.jit(lambda q, k, v: fa._fwd(
        q, k, v, scale=d ** -0.5, window=window, n_meta=0, causal=True,
        block_q=tile[0], block_k=tile[1], interpret=False))
    return f.lower(q, kv, kv).compile().as_text()


@pytest.mark.parametrize("tile", [(128, 128, 128), (1024, 1024, 512)])
def test_matmul_compiles_at_yi6b_ffn(one_chip, tile):
    assert "tpu_custom_call" in compile_matmul(one_chip, 4096, 4096, 11008,
                                               tile)


@pytest.mark.parametrize("tile", [(128, 128), (256, 512), (1024, 1024)])
def test_flash_compiles_at_yi6b_attention(one_chip, tile):
    text = compile_flash(one_chip, 2048, 32, 4, 128, tile)
    assert "tpu_custom_call" in text


def test_flash_compiles_at_danube_sliding_window(one_chip):
    cfg = get_config("h2o-danube-3-4b")
    text = compile_flash(one_chip, 2048, cfg.n_heads, cfg.n_kv_heads,
                         cfg.head_dim, (256, 256), window=cfg.windows[0])
    assert "tpu_custom_call" in text


def _largest_feasible(case):
    bcase = bucket_case(case)
    tiles = feasible_tiles(bcase, candidate_tiles(bcase))
    return bcase, max(tiles, key=lambda t: tile_vmem_bytes(bcase, *t))


def _yi6b_ffn_up():
    return next(c for c in gemm_cases(get_config("yi-6b"), "train_4k")
                if c.label.endswith("/ffn_up"))


def test_largest_feasible_matmul_tile_compiles(one_chip):
    bcase, tile = _largest_feasible(_yi6b_ffn_up())
    assert "tpu_custom_call" in compile_matmul(one_chip, bcase.m, bcase.k,
                                               bcase.n, tile)


def test_largest_feasible_flash_tile_compiles(one_chip):
    case = flash_case(get_config("yi-6b"), "train_4k")
    bcase, tile = _largest_feasible(case)
    text = compile_flash(one_chip, bcase.m, bcase.heads, bcase.heads,
                         bcase.k, tile)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("tile", [(1024, 1024, 1024), (2048, 1024, 512),
                                  (2048, 512, 512), (1024, 256, 2048)])
def test_compiler_refuses_what_the_tuner_rejects(one_chip, tile):
    assert feasible_tiles(_yi6b_ffn_up(), [tile]) == []
    with pytest.raises(Exception, match="vmem"):
        compile_matmul(one_chip, 4096, 4096, 11008, tile)
