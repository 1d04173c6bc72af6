"""The LLaMA-style dense decoder (Yi-6B, DeepSeek-LLM-7B): the trainer's
config for it, its leaves, its loss and its FLOPs.  A configuration file
with ``"family": "dense"`` is run through this module.

The architecture as published: token embedding, then per layer ``x +=
Attn(RMSNorm(x))`` with rotary positions (rotate-half, inverse frequencies
``theta ** (-2i / head_dim)``) and causal grouped-query attention, ``x +=
W_down(silu(W_gate h) * W_up h)`` with ``h = RMSNorm(x)``, a final RMSNorm
and an untied LM head; the loss is the mean next-token cross-entropy.
RMSNorm weights are stored as ``w`` with scale ``1 + w`` (the trainer's
layout), so ``w = 0`` is the published initial scale of 1.

Leaves are named as the trainer names its checkpoint leaves
(``stages/0/u0/attn/wq``: one stage of ``num_hidden_layers`` alike layers),
and every layer is tensor-parallel over a ``model`` axis, Megatron style:
heads, the FFN's hidden units and the vocabulary are split, and the
embedding, the attention output and the FFN output are summed across the
axis.
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

from bench import reference as R

STAGE = "stages/0/u0/"


# ---------------------------------------------------------------- trainer

def trainer_config(c: dict, mix: dict):
    """The trainer's config for the file ``c`` under the mix: the
    architecture at the file's sizes, as the trainer's own options can
    state them."""
    from repro.configs import get_config
    from repro.launch.train import cut_depth

    cfg = cut_depth(get_config(c["arch"]), c["num_hidden_layers"]).replace(
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_head=c["head_dim"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"],
        act=c["hidden_act"], tie_embeddings=c["tie_word_embeddings"],
        param_dtype=c["torch_dtype"], compute_dtype=c["torch_dtype"],
        train_microbatches=mix["microbatches"])
    departs = [k for k, v in (("family", "dense"), ("optimizer", "adamw"),
                              ("moe", None), ("mla", None), ("ssm", None),
                              ("frontend", "none"), ("meta_tokens", 0))
               if getattr(cfg, k) != v]
    if departs:
        raise ValueError(f"{c['arch']}: the trainer's config departs from a "
                         f"dense decoder in {departs}")
    return cfg


# ------------------------------------------------------------------ layout

def specs(cfg: dict) -> dict:
    """name -> (shape, dtype, init std, PartitionSpec over ``model``)."""
    d, h, kv = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    hd, f, v = cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"]
    n = cfg["num_hidden_layers"]
    dt = cfg["torch_dtype"]

    def w(fan_in):
        return min(0.02, fan_in ** -0.5)

    out = {
        "tok_emb": ((v, d), dt, 0.02, P("model", None)),
        "final_norm": ((d,), dt, 0.0, P()),
        "head": ((d, v), dt, w(d), P(None, "model")),
    }
    layer = {
        "ln1": ((n, d), 0.0, P()),
        "attn/wq": ((n, d, h, hd), w(d), P(None, None, "model", None)),
        "attn/wk": ((n, d, kv, hd), w(d), P(None, None, "model", None)),
        "attn/wv": ((n, d, kv, hd), w(d), P(None, None, "model", None)),
        "attn/wo": ((n, h, hd, d), w(h * hd), P(None, "model", None, None)),
        "ln2": ((n, d), 0.0, P()),
        "ffn/wg": ((n, d, f), w(d), P(None, None, "model")),
        "ffn/wi": ((n, d, f), w(d), P(None, None, "model")),
        "ffn/wo": ((n, f, d), w(f), P(None, "model", None)),
    }
    for k, (shape, std, spec) in layer.items():
        out[STAGE + k] = (shape, dt, std, spec)
    return out


def is_matrix(name: str) -> bool:
    """Weight decay applies to matrices: not to the RMSNorm weights."""
    return not (name.endswith("ln1") or name.endswith("ln2")
                or name == "final_norm")


# ------------------------------------------------------------------ model

def loss(cfg, p, tokens, ein, reduce):
    """Mean next-token cross-entropy of one microbatch; runs per shard.
    ``reduce`` sums a layer's partial output across the ``model`` axis."""
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    x = R.embed(p["tok_emb"], tokens, reduce)

    def layer(x, lp):
        h = R.rms_norm(x, lp["ln1"], eps)
        q = R.rope(ein("btd,dhk->bthk", h, lp["attn/wq"]), theta)
        k = R.rope(ein("btd,dhk->bthk", h, lp["attn/wk"]), theta)
        v = ein("btd,dhk->bthk", h, lp["attn/wv"])
        x = x + reduce(ein("bthk,hkd->btd", R.attention(q, k, v, ein),
                           lp["attn/wo"]))
        h = R.rms_norm(x, lp["ln2"], eps)
        u = jax.nn.silu(ein("btd,df->btf", h, lp["ffn/wg"])) \
            * ein("btd,df->btf", h, lp["ffn/wi"])
        return x + reduce(ein("btf,fd->btd", u, lp["ffn/wo"])), None

    stack = {k[len(STAGE):]: val for k, val in p.items()
             if k.startswith(STAGE)}
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, stack)
    x = R.rms_norm(x, p["final_norm"], eps)
    z = ein("btd,dv->btv", x, p["head"])[:, :-1]        # vocab columns here
    return R.cross_entropy(z, tokens[:, 1:])


# ------------------------------------------------------------------ FLOPs

def matmul_params(cfg: dict) -> int:
    """Parameters that enter a matmul: q, k, v, o, the gated FFN and the
    LM head of every layer; not the embedding gather, not the norms."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kv, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    ffn = 3 * d * cfg["intermediate_size"]
    head = d * cfg["vocab_size"]         # a matmul, tied to the table or not
    return cfg["num_hidden_layers"] * (attn + ffn) + head


def step_flops(cfg: dict, mix: dict) -> float:
    """Model FLOPs of one optimizer step over the mix's global batch."""
    tokens = mix["seq_len"] * mix["global_batch"]
    attn_per_token = (12 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
                      * cfg["head_dim"] * mix["seq_len"])
    return float(6 * matmul_params(cfg) * tokens + attn_per_token * tokens)
