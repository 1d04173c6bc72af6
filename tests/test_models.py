"""Per-architecture smoke tests: REDUCED config, one forward/train step on
CPU, asserting output shapes and no NaNs (assignment requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, reduced_config
from repro.models import transformer as tf
from repro.models.layers import init_param_tree


def make_batch(cfg, B=2, T=32, seed=0):
    rng = np.random.default_rng(seed)
    shape = (B, cfg.n_codebooks, T) if cfg.n_codebooks > 1 else (B, T)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, shape))}
    if cfg.frontend == "vision":
        batch["image_embeds"] = jnp.asarray(
            rng.normal(0, 0.02, (B, cfg.image_tokens, cfg.d_model)),
            jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_no_nans(arch):
    cfg = reduced_config(arch)
    params = init_param_tree(tf.param_specs(cfg), jax.random.PRNGKey(0))
    batch = make_batch(cfg)
    logits, hidden, _, _, n_prefix = tf.model_forward(
        cfg, params, batch["tokens"], batch.get("image_embeds"))
    B, T = 2, 32
    if cfg.n_codebooks > 1:
        assert logits.shape == (B, T, cfg.n_codebooks, cfg.vocab)
    else:
        assert logits.shape == (B, T, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))
    assert hidden.shape[-1] == cfg.d_model


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_no_nans(arch):
    from repro.runtime.optim import opt_state_specs
    from repro.runtime.steps import make_train_step
    cfg = reduced_config(arch).replace(train_microbatches=2)
    params = init_param_tree(tf.param_specs(cfg), jax.random.PRNGKey(0))
    opt = init_param_tree(opt_state_specs(cfg, tf.param_specs(cfg)),
                          jax.random.PRNGKey(1))
    batch = jax.tree.map(
        lambda x: jnp.stack([x, x]), make_batch(cfg))   # [m=2, B, ...]
    step = make_train_step(cfg)
    new_p, new_o, metrics = step(params, opt, batch, jnp.asarray(5))
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["gnorm"]))
    # params actually changed
    delta = jax.tree.reduce(
        jnp.add, jax.tree.map(
            lambda a, b: jnp.sum(jnp.abs(a.astype(jnp.float32)
                                         - b.astype(jnp.float32))),
            params, new_p))
    assert float(delta) > 0


def test_loss_near_uniform_at_init():
    cfg = reduced_config("yi-6b")
    params = init_param_tree(tf.param_specs(cfg), jax.random.PRNGKey(0))
    loss, _ = tf.train_loss(cfg, params, make_batch(cfg))
    assert abs(float(loss) - np.log(cfg.vocab)) < 0.5


def test_hymba_meta_tokens_prepended():
    cfg = reduced_config("hymba-1.5b")
    assert cfg.meta_tokens == 8
    params = init_param_tree(tf.param_specs(cfg), jax.random.PRNGKey(0))
    b = make_batch(cfg)
    logits, hidden, _, _, n_prefix = tf.model_forward(cfg, params,
                                                      b["tokens"])
    assert n_prefix == cfg.meta_tokens
    assert hidden.shape[1] == b["tokens"].shape[1] + cfg.meta_tokens
    assert logits.shape[1] == b["tokens"].shape[1]


def test_vision_prefix_masked_from_loss():
    cfg = reduced_config("phi-3-vision-4.2b")
    params = init_param_tree(tf.param_specs(cfg), jax.random.PRNGKey(0))
    b = make_batch(cfg)
    # image embeddings change logits but loss stays aligned to text tokens
    loss1, _ = tf.train_loss(cfg, params, b)
    b2 = dict(b)
    b2["image_embeds"] = b["image_embeds"] * 2.0
    loss2, _ = tf.train_loss(cfg, params, b2)
    assert np.isfinite(float(loss1)) and np.isfinite(float(loss2))
    assert abs(float(loss1) - float(loss2)) > 0          # prefix is attended


def test_gemma3_local_global_pattern():
    from repro.configs import get_config
    cfg = get_config("gemma3-27b")
    wins = cfg.layer_windows
    assert sum(1 for w in wins if w == 0) == 10          # 10 global layers
    assert all(wins[i] == 0 for i in range(5, 62, 6))
    stages = tf.build_stages(cfg)
    assert [(len(s.unit), s.repeat) for s in stages] == [(6, 10), (1, 2)]


def test_deepseek_v3_stage_split():
    from repro.configs import get_config
    stages = tf.build_stages(get_config("deepseek-v3-671b"))
    assert [(len(s.unit), s.repeat) for s in stages] == [(1, 3), (1, 58)]
    assert not stages[0].unit[0].moe and stages[1].unit[0].moe


def test_embedding_gradient_sums_repeated_ids_in_float32():
    # one id repeated 4096 times, as a frequent token repeats in Zipfian
    # text: a bf16 scatter-add of the gradient would stall far below the
    # sum once it is ~256x larger than each term
    table = jax.random.normal(jax.random.PRNGKey(0), (64, 8), jnp.bfloat16)
    ids = jnp.concatenate([jnp.zeros(4096, jnp.int32), jnp.arange(64)])
    ct = jax.random.normal(jax.random.PRNGKey(1), (ids.size, 8)) ** 2

    def grad(f, t):
        return jax.grad(lambda t: jnp.sum(f(t, ids).astype(jnp.float32)
                                          * ct))(t)

    want = np.asarray(grad(lambda t, i: t[i], table.astype(jnp.float32)))
    got = np.asarray(grad(tf.gather_rows, table), np.float32)
    plain = np.asarray(grad(lambda t, i: jnp.take(t, i, axis=0), table),
                       np.float32)
    np.testing.assert_allclose(got, want, rtol=1e-2)
    assert np.abs(plain[0] - want[0]).max() > 0.1 * np.abs(want[0]).max()
