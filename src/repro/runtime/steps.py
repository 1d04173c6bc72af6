"""Step functions (train / prefill / decode) and their abstract input specs.

``input_specs(cfg, shape)`` produces the exact ``ParamSpec`` tree the step
lowers against -- weak-type-correct, shardable, with **no device
allocation** -- which is what the multi-pod dry-run feeds to
``jax.jit(...).lower()``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import transformer as tf
from repro.models.layers import ParamSpec
from repro.runtime import shardctx
from repro.runtime.optim import cosine_schedule, opt_update


def _maybe_scope(ctx):
    if ctx is None:
        import contextlib
        return contextlib.nullcontext()
    return shardctx.scope(*ctx)


@dataclasses.dataclass(frozen=True)
class TrainHParams:
    peak_lr: float = 3e-4
    warmup: int = 100
    total_steps: int = 10_000


# ---------------------------------------------------------------------------
# Abstract input specs per (arch x shape) cell
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeConfig, *,
                microbatches: int | None = None) -> dict:
    """ParamSpec tree of the step inputs for one dry-run cell."""
    b, t = shape.global_batch, shape.seq_len

    if shape.kind == "train":
        m = microbatches if microbatches is not None else cfg.train_microbatches
        assert b % m == 0, (b, m)
        mb = b // m
        t_text = t - (cfg.image_tokens if cfg.frontend == "vision" else 0)
        if cfg.n_codebooks > 1:
            toks = ParamSpec((m, mb, cfg.n_codebooks, t_text),
                             (None, "batch", None, None), "int32")
        else:
            toks = ParamSpec((m, mb, t_text), (None, "batch", None), "int32")
        specs = {"tokens": toks}
        if cfg.frontend == "vision":
            specs["image_embeds"] = ParamSpec(
                (m, mb, cfg.image_tokens, cfg.d_model),
                (None, "batch", None, None), cfg.compute_dtype)
        return specs

    if shape.kind == "prefill":
        t_text = t - (cfg.image_tokens if cfg.frontend == "vision" else 0) \
            - cfg.meta_tokens
        if cfg.n_codebooks > 1:
            toks = ParamSpec((b, cfg.n_codebooks, t_text),
                             ("batch", None, None), "int32")
        else:
            toks = ParamSpec((b, t_text), ("batch", None), "int32")
        specs = {"tokens": toks}
        if cfg.frontend == "vision":
            specs["image_embeds"] = ParamSpec(
                (b, cfg.image_tokens, cfg.d_model),
                ("batch", None, None), cfg.compute_dtype)
        return specs

    # decode: one new token against a cache of capacity seq_len
    if cfg.n_codebooks > 1:
        toks = ParamSpec((b, cfg.n_codebooks, 1), ("batch", None, None), "int32")
    else:
        toks = ParamSpec((b, 1), ("batch", None), "int32")
    return {"tokens": toks, "cache": tf.cache_specs(cfg, b, t)}


# ---------------------------------------------------------------------------
# Train step (with gradient accumulation)
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, hp: TrainHParams = TrainHParams(), *,
                    use_flash: bool = False, compress_fn=None,
                    shard_ctx=None):
    """Returns train_step(params, opt_state, batch, step) -> (p, s, metrics).
    ``metrics["grad_norms"]`` holds each parameter's gradient norm, before
    clipping, in the tree of the parameters.

    ``batch`` leaves carry a leading microbatch axis; gradients accumulate
    across microbatches in ``cfg.grad_accum_dtype`` via ``lax.scan``.
    ``compress_fn`` optionally transforms the accumulated gradient tree
    (gradient compression; see runtime/compress.py).

    With a float32 accumulator the token embedding's gradient is taken with
    respect to the rows the microbatch gathers and scatter-added into the
    accumulator row by row: the table's dense gradient, a zero-filled
    table per microbatch added whole, is never formed (a tied table still
    gets its head's dense gradient).  A bfloat16 scatter-add would lose a
    repeated id's sum (``tf.gather_rows``), and a single microbatch has no
    accumulator: those take the table's dense gradient.
    """
    n_micro = cfg.train_microbatches
    acc_dt = jnp.dtype(cfg.grad_accum_dtype)
    scatter_rows = acc_dt == jnp.float32

    def micro_grads(params, mb):
        def loss_fn(p):
            return tf.train_loss(cfg, p, mb, use_flash=use_flash)
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        return loss, grads

    def micro_row_grads(params, mb):
        """The loss, the gradients of the parameters but an untied
        ``tok_emb``, and the float32 gradient of the rows gathered from
        ``tok_emb`` with their index."""
        table = params["tok_emb"]
        idx = tf.embedding_index(cfg, mb["tokens"])
        with jax.named_scope("embed"):   # f32 rows: their gradient comes in f32
            rows = table[idx].astype(acc_dt)

        def loss_fn(p, rows):
            with jax.named_scope("embed"):
                x0 = rows.astype(table.dtype)
            return tf.train_loss(cfg, p, mb, use_flash=use_flash, rows=x0)
        dp = params if cfg.tie_embeddings else \
            {k: v for k, v in params.items() if k != "tok_emb"}
        (loss, metrics), (grads, drows) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True)(dp, rows)
        return loss, grads, (idx, drows)

    def train_step(params, opt_state, batch, step):
      with _maybe_scope(shard_ctx):
        lr = cosine_schedule(step, peak_lr=hp.peak_lr, warmup=hp.warmup,
                             total=hp.total_steps)
        if n_micro == 1:
            mb = jax.tree.map(lambda x: x[0], batch)
            loss, grads = micro_grads(params, mb)
        else:
            with jax.named_scope("grad_accum"):
                zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, acc_dt),
                                     params)

            def body(carry, mb):
                gacc, lsum = carry
                if scatter_rows:
                    loss, g, (idx, drows) = micro_row_grads(params, mb)
                else:
                    loss, g = micro_grads(params, mb)
                with jax.named_scope("grad_accum"):
                    gacc = {k: jax.tree.map(lambda x, y: x + y.astype(acc_dt),
                                            a, g[k]) if k in g else a
                            for k, a in gacc.items()}
                if scatter_rows:
                    with jax.named_scope("embed"):
                        gacc["tok_emb"] = gacc["tok_emb"].at[idx].add(drows)
                return (gacc, lsum + loss), ()

            (grads, lsum), _ = jax.lax.scan(body, (zeros, jnp.zeros((), jnp.float32)),
                                            batch)
            with jax.named_scope("grad_accum"):
                loss = lsum / n_micro
                grads = jax.tree.map(lambda g: g / n_micro, grads)
        if compress_fn is not None:
            grads = compress_fn(grads)
        with jax.named_scope("optimizer"):
            grad_norms = jax.tree.map(
                lambda g: jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32)))),
                grads)
            new_params, new_state, gnorm = opt_update(cfg, grads, opt_state,
                                                      params, lr)
        metrics = {"loss": loss, "gnorm": gnorm, "grad_norms": grad_norms,
                   "lr": lr, "step": step.astype(jnp.int32) + 1}
        return new_params, new_state, metrics

    return train_step


# ---------------------------------------------------------------------------
# Serve steps
# ---------------------------------------------------------------------------

def make_prefill_step(cfg: ModelConfig, *, use_flash: bool = False,
                      shard_ctx=None):
    def prefill_step(params, batch):
        with _maybe_scope(shard_ctx):
            return tf.prefill(cfg, params, batch["tokens"],
                              batch.get("image_embeds"), use_flash=use_flash)
    return prefill_step


def make_decode_step(cfg: ModelConfig, *, shard_ctx=None):
    def decode_step(params, batch):
        with _maybe_scope(shard_ctx):
            logits, cache = tf.decode_step(cfg, params, batch["cache"],
                                           batch["tokens"])
            return logits, cache
    return decode_step


def step_fn_for(cfg: ModelConfig, shape: ShapeConfig, *, use_flash=False,
                microbatches: int | None = None, shard_ctx=None):
    """The (callable, donate_argnums) pair a dry-run cell lowers."""
    if shape.kind == "train":
        c = cfg if microbatches is None else \
            cfg.replace(train_microbatches=microbatches)
        return make_train_step(c, use_flash=use_flash,
                               shard_ctx=shard_ctx), (0, 1)
    if shape.kind == "prefill":
        return make_prefill_step(cfg, use_flash=use_flash,
                                 shard_ctx=shard_ctx), ()
    return make_decode_step(cfg, shard_ctx=shard_ctx), (1,)  # donate cache
