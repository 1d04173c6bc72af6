"""Generic decoder: assembles every assigned architecture from its config.

The layer sequence is decomposed into *stages*: maximal periodic runs of a
repeating unit of layer descriptors.  Each stage is executed as a
``lax.scan`` over the repeat axis with the unit unrolled inside the body
(e.g. gemma3's 5-local:1-global pattern becomes one scan of 10 over a
6-layer unit).  This keeps the HLO small enough to SPMD-partition a
512-device mesh while giving every layer class its own cache shape
(windowed ring vs full vs SSM state vs MLA latent).

All functions are pure; parameters / caches are pytrees whose *specs*
(shape, dtype, logical sharding axes) are computed without allocation so the
dry-run can lower against ``jax.ShapeDtypeStruct`` trees.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    ParamSpec,
    cross_entropy,
    mlp,
    mlp_spec,
    rms_norm,
)
from repro.runtime.shardctx import constrain


# ---------------------------------------------------------------------------
# Stage decomposition
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class LayerDesc:
    kind: str                      # attn | ssm | hybrid
    window: int                    # 0 = global
    moe: bool
    theta: float


@dataclass(frozen=True)
class Stage:
    unit: tuple                    # tuple[LayerDesc]
    repeat: int

    @property
    def n_layers(self) -> int:
        return len(self.unit) * self.repeat


def layer_descs(cfg: ModelConfig):
    kinds, wins, moes = cfg.kinds, cfg.layer_windows, cfg.layer_moe
    out = []
    for i in range(cfg.n_layers):
        theta = cfg.rope_theta
        if wins[i] > 0 and cfg.local_rope_theta:
            theta = cfg.local_rope_theta
        out.append(LayerDesc(kinds[i], wins[i], moes[i], theta))
    return out


def build_stages(cfg: ModelConfig, max_unit: int = 8):
    """Greedy periodic decomposition of the layer sequence."""
    descs = layer_descs(cfg)
    n = len(descs)
    stages, i = [], 0
    while i < n:
        best_ul, best_r = 1, 1
        for ul in range(1, min(max_unit, n - i) + 1):
            unit = descs[i:i + ul]
            r = 1
            while descs[i + r * ul: i + (r + 1) * ul] == unit:
                r += 1
            if r >= 2 and ul * r > best_ul * best_r:
                best_ul, best_r = ul, r
        stages.append(Stage(tuple(descs[i:i + best_ul]), best_r))
        i += best_ul * best_r
    return stages


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

def _layer_spec(cfg: ModelConfig, desc: LayerDesc, lead: tuple):
    d = cfg.d_model
    la = ("layers",) * len(lead)
    dt = cfg.param_dtype
    spec = {"ln1": ParamSpec(lead + (d,), la + (None,), dt, init="zeros")}
    if desc.kind in ("attn", "hybrid"):
        spec["attn"] = (attn.mla_spec(cfg, lead) if cfg.mla is not None
                        else attn.gqa_spec(cfg, lead))
    if desc.kind in ("ssm", "hybrid"):
        spec["ssm"] = ssm_mod.ssm_spec(cfg, lead)
    if desc.kind == "hybrid":
        spec["ln_a"] = ParamSpec(lead + (d,), la + (None,), dt, init="zeros")
        spec["ln_s"] = ParamSpec(lead + (d,), la + (None,), dt, init="zeros")
    if desc.kind != "ssm":                       # mamba block has no extra FFN
        spec["ln2"] = ParamSpec(lead + (d,), la + (None,), dt, init="zeros")
        if desc.moe:
            spec["ffn"] = moe_mod.moe_spec(cfg, lead)
        else:
            dff = cfg.dense_d_ff if (cfg.moe is not None) else cfg.d_ff
            spec["ffn"] = mlp_spec(d, dff, dt, stacked=lead[0] if lead else None)
    return spec


def param_specs(cfg: ModelConfig):
    d, v = cfg.d_model, cfg.vocab
    dt = cfg.param_dtype
    spec = {}
    if cfg.n_codebooks > 1:
        spec["tok_emb"] = ParamSpec((cfg.n_codebooks, v, d),
                                    (None, "vocab", "embed"), dt)
    else:
        spec["tok_emb"] = ParamSpec((v, d), ("vocab", "embed"), dt)
    if cfg.meta_tokens:
        spec["meta"] = ParamSpec((cfg.meta_tokens, d), (None, "embed"), dt)
    if cfg.frontend == "vision":
        spec["img_proj"] = ParamSpec((d, d), ("embed", "embed_out"), dt)

    stages = build_stages(cfg)
    sspecs = []
    for st in stages:
        lead = (st.repeat,)
        sspecs.append({f"u{j}": _layer_spec(cfg, desc, lead)
                       for j, desc in enumerate(st.unit)})
    spec["stages"] = tuple(sspecs)
    spec["final_norm"] = ParamSpec((d,), (None,), dt, init="zeros")
    if not cfg.tie_embeddings:
        if cfg.n_codebooks > 1:
            spec["head"] = ParamSpec((cfg.n_codebooks, d, v),
                                     (None, "embed", "vocab"), dt)
        else:
            spec["head"] = ParamSpec((d, v), ("embed", "vocab"), dt)
    if cfg.mtp_depth:
        dff = cfg.dense_d_ff or cfg.d_ff or 4 * d
        mdesc = LayerDesc("attn", 0, False, cfg.rope_theta)
        blk = _layer_spec(cfg, mdesc, ())
        blk["ffn"] = mlp_spec(d, dff, dt)        # dense FFN even in MoE archs
        spec["mtp"] = {
            "proj": ParamSpec((2 * d, d), (None, "embed_out"), dt),
            "ln_h": ParamSpec((d,), (None,), dt, init="zeros"),
            "ln_e": ParamSpec((d,), (None,), dt, init="zeros"),
            "block": blk,
            "ln_out": ParamSpec((d,), (None,), dt, init="zeros"),
        }
    return spec


# ---------------------------------------------------------------------------
# Layer forward (full-sequence)
# ---------------------------------------------------------------------------

@jax.named_scope("attention")
def _attn_forward(cfg, desc, p, h, positions, n_meta, collect, use_flash):
    if cfg.mla is not None:
        if collect:
            return attn.mla_forward(cfg, p["attn"], h, positions,
                                    n_meta=n_meta, return_latent=True)
        return attn.mla_forward(cfg, p["attn"], h, positions, n_meta=n_meta), None
    if collect:
        out, kv = attn.gqa_forward(p["attn"], h, positions, window=desc.window,
                                   theta=desc.theta, n_meta=n_meta,
                                   return_kv=True, use_flash=use_flash)
        return out, kv
    return attn.gqa_forward(p["attn"], h, positions, window=desc.window,
                            theta=desc.theta, n_meta=n_meta,
                            use_flash=use_flash), None


def _ring_pack(k, window, n_meta):
    """Pack full-sequence keys/values into a ring cache of capacity window."""
    b, t, kv, dh = k.shape
    w = min(window, max(t - n_meta, 1))
    start = max(n_meta, t - w)
    positions = jnp.arange(start, t)
    ring = jnp.zeros((b, window, kv, dh), k.dtype)
    return ring.at[:, positions % window].set(k[:, start:])


def layer_forward(cfg, desc, p, x, positions, n_meta, *, collect=False,
                  use_flash=False):
    """One layer, full sequence.  Returns (x, cache_entry, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    entry = {}

    if desc.kind == "attn":
        out, kv = _attn_forward(cfg, desc, p, h, positions, n_meta, collect,
                                use_flash)
        x = x + out
    elif desc.kind == "ssm":
        if collect:
            out, st = ssm_mod.ssd_forward(cfg, p["ssm"], h, return_state=True)
            entry.update(st)
        else:
            out = ssm_mod.ssd_forward(cfg, p["ssm"], h)
        return x + out, entry, aux                # mamba block: no extra FFN
    else:                                         # hybrid: parallel attn + ssm
        a_out, kv = _attn_forward(cfg, desc, p, h, positions, n_meta, collect,
                                  use_flash)
        if collect:
            s_out, st = ssm_mod.ssd_forward(cfg, p["ssm"], h, return_state=True)
            entry.update(st)
        else:
            s_out = ssm_mod.ssd_forward(cfg, p["ssm"], h)
        out = 0.5 * (rms_norm(a_out, p["ln_a"], cfg.norm_eps)
                     + rms_norm(s_out, p["ln_s"], cfg.norm_eps))
        x = x + out

    if collect and desc.kind in ("attn", "hybrid"):
        if cfg.mla is not None:
            entry["ckv"], entry["krope"] = kv
        else:
            k, v = kv
            if desc.window > 0:
                entry["k"] = _ring_pack(k, desc.window, n_meta)
                entry["v"] = _ring_pack(v, desc.window, n_meta)
                if n_meta:
                    entry["k_pre"] = k[:, :n_meta]
                    entry["v_pre"] = v[:, :n_meta]
            else:
                entry["k"], entry["v"] = k, v

    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    with jax.named_scope("ffn"):
        if desc.moe:
            y, aux = moe_mod.moe_apply(cfg, p["ffn"], h2, cfg.moe.router)
        else:
            y = mlp(p["ffn"], h2, cfg.act)
    return x + y, entry, aux


# ---------------------------------------------------------------------------
# Layer decode (single token against cache)
# ---------------------------------------------------------------------------

def layer_decode(cfg, desc, p, x, cache, pos):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    new = {}
    if desc.kind == "attn":
        if cfg.mla is not None:
            out, nc = attn.mla_decode(cfg, p["attn"], h, cache, pos)
        else:
            out, nc = attn.gqa_decode(p["attn"], h, cache, pos,
                                      window=desc.window, theta=desc.theta,
                                      n_meta=0)
        new.update(nc)
        x = x + out
    elif desc.kind == "ssm":
        out, nc = ssm_mod.ssd_decode(cfg, p["ssm"], h, cache)
        new.update(nc)
        return x + out, new
    else:                                         # hybrid
        a_out, nca = attn.gqa_decode(p["attn"], h,
                                     {k: v for k, v in cache.items()
                                      if k in ("k", "v", "k_pre", "v_pre")},
                                     pos, window=desc.window, theta=desc.theta,
                                     n_meta=0)
        s_out, ncs = ssm_mod.ssd_decode(
            cfg, p["ssm"], h, {"state": cache["state"], "conv": cache["conv"]})
        new.update(nca)
        new.update(ncs)
        out = 0.5 * (rms_norm(a_out, p["ln_a"], cfg.norm_eps)
                     + rms_norm(s_out, p["ln_s"], cfg.norm_eps))
        x = x + out

    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    if desc.moe:
        y, _ = moe_mod.moe_apply(cfg, p["ffn"], h2, cfg.moe.router)
    else:
        y = mlp(p["ffn"], h2, cfg.act)
    return x + y, new


# ---------------------------------------------------------------------------
# Stage execution
# ---------------------------------------------------------------------------

def stage_forward(cfg, stage: Stage, sp, x, positions, n_meta, *,
                  collect=False, use_flash=False):
    def body(carry, up):
        h, aux = carry
        entries = {}
        for j, desc in enumerate(stage.unit):
            h, e, a = layer_forward(cfg, desc, up[f"u{j}"], h, positions,
                                    n_meta, collect=collect,
                                    use_flash=use_flash)
            entries[f"u{j}"] = e
            aux = aux + a
        return (h, aux), entries

    if cfg.remat:
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if cfg.remat_policy == "dots" else None)
        body = jax.checkpoint(body, policy=policy)
    (x, aux), caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), sp,
                                    unroll=stage.repeat if cfg.scan_unroll
                                    else 1)
    return x, caches, aux


def stage_decode(cfg, stage: Stage, sp, x, cache, pos):
    def body(h, xs):
        up, uc = xs
        new = {}
        for j, desc in enumerate(stage.unit):
            h, nc = layer_decode(cfg, desc, up[f"u{j}"], h, uc[f"u{j}"], pos)
            new[f"u{j}"] = nc
        return h, new

    x, new_cache = jax.lax.scan(body, x, (sp, cache))
    return x, new_cache


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

@jax.custom_vjp
def gather_rows(table, ids):
    """``table[ids]``, whose gradient sums the rows in float32.

    The plain gather's gradient scatter-adds in the table's dtype.  In
    bf16 a frequent token's row then loses most of its sum: with
    Zipfian text one id fills a fifth of a 2048-token row, and its
    gradient came out 12% short in norm."""
    return jnp.take(table, ids, axis=0)


def _gather_rows_fwd(table, ids):
    return jnp.take(table, ids, axis=0), (table, ids)


def _gather_rows_bwd(res, ct):
    table, ids = res
    g = jnp.zeros(table.shape, jnp.float32).at[ids].add(
        ct.astype(jnp.float32))
    return g.astype(table.dtype), None


gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


def embedding_index(cfg: ModelConfig, tokens):
    """The index of the rows of ``tok_emb`` that ``tokens`` look up:
    ``tok_emb[idx]`` is [B,T,D], or [B,K,T,D] with a row per codebook."""
    if cfg.n_codebooks > 1:                       # musicgen: [B,K,T], table [K,V,D]
        return (jnp.arange(cfg.n_codebooks)[:, None], tokens)
    return (tokens,)


@jax.named_scope("embed")
def embed_tokens(cfg: ModelConfig, params, tokens, rows=None):
    """The input embeddings of ``tokens``.  ``rows`` are the table's rows
    ``tok_emb[embedding_index(cfg, tokens)]`` where the caller gathered
    them (and takes their gradient itself); else ``gather_rows`` gathers
    them here."""
    if rows is not None:
        x = (sum(rows[:, k] for k in range(cfg.n_codebooks))
             if cfg.n_codebooks > 1 else rows)
    elif cfg.n_codebooks > 1:
        x = sum(gather_rows(params["tok_emb"][k], tokens[:, k])
                for k in range(cfg.n_codebooks))
    else:
        x = gather_rows(params["tok_emb"], tokens)
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


@jax.named_scope("head_loss")       # shared with the train loss
def lm_head(cfg: ModelConfig, params, x):
    if cfg.tie_embeddings:
        out = jnp.einsum("btd,vd->btv", x, params["tok_emb"])
    elif cfg.n_codebooks > 1:
        out = jnp.einsum("btd,kdv->btkv", x, params["head"])
        return constrain(out, ("batch", None, None, "vocab"))
    else:
        out = jnp.einsum("btd,dv->btv", x, params["head"])
    return constrain(out, ("batch", None, "vocab"))


# ---------------------------------------------------------------------------
# Full forward / prefill / decode / loss
# ---------------------------------------------------------------------------

def model_forward(cfg: ModelConfig, params, tokens, image_embeds=None, *,
                  collect=False, use_flash=False, rows=None):
    """Returns (logits, hidden, caches, aux).  ``rows``: as in
    ``embed_tokens``."""
    x = embed_tokens(cfg, params, tokens, rows)
    n_prefix = 0
    if cfg.frontend == "vision" and image_embeds is not None:
        img = image_embeds.astype(x.dtype) @ params["img_proj"]
        x = jnp.concatenate([img, x], axis=1)
        n_prefix = img.shape[1]
    if cfg.meta_tokens:
        meta = jnp.broadcast_to(params["meta"][None],
                                (x.shape[0],) + params["meta"].shape)
        x = jnp.concatenate([meta.astype(x.dtype), x], axis=1)
        n_prefix = cfg.meta_tokens
    t_total = x.shape[1]
    positions = jnp.arange(t_total)
    n_meta = cfg.meta_tokens                     # window-exempt prefix length

    stages = build_stages(cfg)
    caches, aux = [], jnp.zeros((), jnp.float32)
    for si, st in enumerate(stages):
        x, c, a = stage_forward(cfg, st, params["stages"][si], x, positions,
                                n_meta, collect=collect, use_flash=use_flash)
        caches.append(c)
        aux = aux + a
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_head(cfg, params, x[:, n_prefix:])
    return logits, x, tuple(caches), aux, n_prefix


def prefill(cfg: ModelConfig, params, tokens, image_embeds=None,
            use_flash=False):
    """Full-sequence forward collecting decode caches.

    Returns (last_logits, cache) where cache = {"stages": ..., "pos": T}.
    """
    logits, _, caches, _, n_prefix = model_forward(
        cfg, params, tokens, image_embeds, collect=True, use_flash=use_flash)
    t_total = (tokens.shape[-1] + n_prefix)
    cache = {"stages": caches, "pos": jnp.asarray(t_total, jnp.int32)}
    return logits[:, -1:], cache


def decode_step(cfg: ModelConfig, params, cache, tokens_new):
    """One decode step. tokens_new: [B,1] (or [B,K,1] audio)."""
    x = embed_tokens(cfg, params, tokens_new)
    pos = cache["pos"]
    stages = build_stages(cfg)
    new_stage_caches = []
    for si, st in enumerate(stages):
        x, nc = stage_decode(cfg, st, params["stages"][si], x,
                             cache["stages"][si], pos)
        new_stage_caches.append(nc)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_head(cfg, params, x)
    return logits, {"stages": tuple(new_stage_caches), "pos": pos + 1}


def _mtp_loss(cfg, params, hidden, tokens, n_prefix, rows=None):
    """DeepSeek-V3 multi-token prediction (depth 1) auxiliary loss."""
    mp = params["mtp"]
    h = hidden[:, n_prefix:]                      # [B,T,D] text region
    emb = embed_tokens(cfg, params, tokens, rows)
    h_in = jnp.concatenate(
        [rms_norm(h[:, :-1], mp["ln_h"], cfg.norm_eps),
         rms_norm(emb[:, 1:], mp["ln_e"], cfg.norm_eps)], axis=-1) @ mp["proj"]
    positions = jnp.arange(h_in.shape[1])
    desc = LayerDesc("attn", 0, False, cfg.rope_theta)
    h1, _, _ = layer_forward(cfg, desc, mp["block"], h_in, positions, 0)
    h1 = rms_norm(h1, mp["ln_out"], cfg.norm_eps)
    logits = lm_head(cfg, params, h1)             # [B,T-1,V]
    return cross_entropy(logits[:, :-1], tokens[:, 2:])


def train_loss(cfg: ModelConfig, params, batch, use_flash=False, rows=None):
    """batch: {"tokens": [B,T] | [B,K,T], "image_embeds"?: [B,P,D]}.
    ``rows``: as in ``embed_tokens``; with them an untied ``tok_emb`` may be
    left out of ``params``."""
    tokens = batch["tokens"]
    logits, hidden, _, aux, n_prefix = model_forward(
        cfg, params, tokens, batch.get("image_embeds"), use_flash=use_flash,
        rows=rows)
    with jax.named_scope("head_loss"):
        if cfg.n_codebooks > 1:
            losses = [cross_entropy(logits[:, :-1, k], tokens[:, k, 1:])
                      for k in range(cfg.n_codebooks)]
            loss = sum(losses) / cfg.n_codebooks
        else:
            loss = cross_entropy(logits[:, :-1], tokens[:, 1:])
    metrics = {"ce": loss}
    if cfg.moe is not None:
        loss = loss + cfg.moe_aux_coef * aux
        metrics["aux"] = aux
    if cfg.mtp_depth:
        mtp = _mtp_loss(cfg, params, hidden, tokens, n_prefix, rows)
        loss = loss + cfg.mtp_loss_weight * mtp
        metrics["mtp"] = mtp
    metrics["loss"] = loss
    return loss, metrics


def grow_cache(cfg: ModelConfig, cache, capacity: int):
    """Pad full-attention / MLA caches along the sequence axis to ``capacity``.

    Ring (windowed) caches and SSM states are already fixed-size.  Call after
    :func:`prefill` to make room for decode steps.
    """
    stages = build_stages(cfg)
    new_stages = []
    for si, st in enumerate(stages):
        sc = dict(cache["stages"][si])
        for j, desc in enumerate(st.unit):
            e = dict(sc[f"u{j}"])
            if desc.kind in ("attn", "hybrid"):
                keys = ("ckv", "krope") if cfg.mla is not None else \
                    (("k", "v") if desc.window == 0 else ())
                for kk in keys:
                    arr = e[kk]
                    pad = capacity - arr.shape[2]      # [R,B,S,...]
                    if pad > 0:
                        widths = [(0, 0)] * arr.ndim
                        widths[2] = (0, pad)
                        e[kk] = jnp.pad(arr, widths)
            sc[f"u{j}"] = e
        new_stages.append(sc)
    return {"stages": tuple(new_stages), "pos": cache["pos"]}


# ---------------------------------------------------------------------------
# Cache specs (for dry-run decode cells)
# ---------------------------------------------------------------------------

def cache_specs(cfg: ModelConfig, batch: int, seq_len: int):
    """ParamSpec tree matching prefill()'s cache layout at capacity seq_len."""
    kvd = cfg.head_dim
    dt = cfg.compute_dtype
    stages = build_stages(cfg)
    out = []
    for st in stages:
        lead = (st.repeat,)
        la = ("layers",)
        sdict = {}
        for j, desc in enumerate(st.unit):
            e = {}
            if desc.kind in ("attn", "hybrid"):
                if cfg.mla is not None:
                    m = cfg.mla
                    e["ckv"] = ParamSpec(lead + (batch, seq_len, m.kv_lora_rank),
                                         la + ("batch", "kv_seq", None), dt)
                    e["krope"] = ParamSpec(lead + (batch, seq_len, m.qk_rope_dim),
                                           la + ("batch", "kv_seq", None), dt)
                else:
                    cap = min(desc.window, seq_len) if desc.window else seq_len
                    shp = lead + (batch, cap, cfg.n_kv_heads, kvd)
                    ax = la + ("batch", "kv_seq", "kv", None)
                    e["k"] = ParamSpec(shp, ax, dt)
                    e["v"] = ParamSpec(shp, ax, dt)
                    if cfg.meta_tokens and desc.window:
                        pshp = lead + (batch, cfg.meta_tokens, cfg.n_kv_heads, kvd)
                        pax = la + ("batch", None, "kv", None)
                        e["k_pre"] = ParamSpec(pshp, pax, dt)
                        e["v_pre"] = ParamSpec(pshp, pax, dt)
            if desc.kind in ("ssm", "hybrid"):
                s, d_in, nh, conv_dim = ssm_mod._dims(cfg)
                e["state"] = ParamSpec(lead + (batch, nh, s.head_dim, s.d_state),
                                       la + ("batch", "heads", None, None),
                                       "float32")
                e["conv"] = ParamSpec(lead + (batch, s.d_conv - 1, conv_dim),
                                      la + ("batch", None, "ffn"), dt)
            sdict[f"u{j}"] = e
        out.append(sdict)
    return {"stages": tuple(out),
            "pos": ParamSpec((), (), "int32", init="zeros")}
