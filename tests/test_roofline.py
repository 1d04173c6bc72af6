"""Analytic roofline model validated against XLA cost analysis.

The production dry-run cannot use ``cost_analysis`` FLOPs directly (XLA
counts while-loop bodies once; EXPERIMENTS.md §Dry-run) -- here we unroll
the layer scans on reduced configs so XLA counts everything, then require
the analytic model to agree within tolerance."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import SHAPES, reduced_config, get_config
from repro.core.roofline import cell_roofline, forward_flops, model_flops
from repro.models import transformer as tf
from repro.models.layers import spec_tree_to_sds


def xla_forward_flops(cfg, B, T):
    cfg = cfg.replace(scan_unroll=True, remat=False)
    pspecs = spec_tree_to_sds(tf.param_specs(cfg))
    shape = (B, cfg.n_codebooks, T) if cfg.n_codebooks > 1 else (B, T)
    toks = jax.ShapeDtypeStruct(shape, jnp.int32)

    def fwd(p, t):
        logits, *_ = tf.model_forward(cfg, p, t)
        return logits

    c = jax.jit(fwd).lower(pspecs, toks).compile()
    return c.cost_analysis()["flops"]


@pytest.mark.parametrize("arch", ["yi-6b", "mixtral-8x7b", "mamba2-370m",
                                  "musicgen-large"])
def test_analytic_flops_match_xla(arch):
    cfg = reduced_config(arch).replace(param_dtype="float32",
                                       compute_dtype="float32")
    B, T = 2, 64
    got = forward_flops(cfg, B * T, T, "train")
    want = xla_forward_flops(cfg, B, T)
    # attention-mask/elementwise ops make XLA a bit larger; matmuls dominate
    assert want * 0.5 < got < want * 1.5, (arch, got, want)


def test_model_flops_convention():
    cfg = get_config("yi-6b")
    tokens = 1024
    assert model_flops(cfg, tokens, "train") == pytest.approx(
        6 * cfg.n_params() * tokens)
    mx = get_config("mixtral-8x7b")
    assert model_flops(mx, tokens, "train") == pytest.approx(
        6 * mx.n_active_params() * tokens)


def test_cell_roofline_terms_positive_and_dominant():
    for arch in ("yi-6b", "deepseek-v3-671b", "mamba2-370m"):
        cfg = get_config(arch)
        for shape_name in ("train_4k", "decode_32k"):
            if shape_name in cfg.skip_shapes:
                continue
            r = cell_roofline(cfg, SHAPES[shape_name],
                              {"data": 16, "model": 16})
            assert r["compute_s"] > 0 and r["memory_s"] > 0
            assert r["dominant"] in ("compute_s", "memory_s", "collective_s")
            assert 0 < r["useful_ratio"] < 1.6


def test_decode_is_memory_or_collective_bound():
    """Sanity: single-token decode can never be compute-bound on v5e."""
    cfg = get_config("yi-6b")
    r = cell_roofline(cfg, SHAPES["decode_32k"], {"data": 16, "model": 16})
    assert r["dominant"] != "compute_s"


def test_train_compute_term_scales_with_chips():
    cfg = get_config("yi-6b")
    r1 = cell_roofline(cfg, SHAPES["train_4k"], {"data": 16, "model": 16})
    r2 = cell_roofline(cfg, SHAPES["train_4k"],
                       {"pod": 2, "data": 16, "model": 16})
    assert r2["compute_s"] == pytest.approx(r1["compute_s"] / 2)
