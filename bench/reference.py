"""Plain float32 reference of the first two AdamW train steps, independent of
the program.  The architecture is the configuration's family
(``bench/families/<family>.py``: its leaves, which of them decay, and one
microbatch's loss); this module is the driver every family shares, and the
pieces a family's loss is built from.

Departures from the published recipe, each for the comparison's sake:

* weights are held as bfloat16 values, as the configuration states; each
  update is computed in float32 and rounded to bfloat16;
* AdamW (b1 0.9, b2 0.95, eps 1e-8, weight decay 0.1 on the family's
  matrices, global gradient clipping at 1.0) and the cosine schedule are
  the training recipe's, read from the traffic mix.

Every leaf is laid out over a ``model`` axis of as many devices as the cell
has chips, by the family's ``PartitionSpec``; the loss runs per shard under
``shard_map``.  Matmuls run at ``HIGHEST`` precision.  ``precision="fp8"``
is the control: every matmul operand, forward and backward, rounded to
float8_e4m3 under a per-tensor scale.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0                        # largest float8_e4m3fn value
ATTN_BLOCK = 512                       # query rows per attention block


# ------------------------------------------------------------------ layout

def make_mesh(chips: int) -> Mesh:
    return Mesh(np.array(jax.devices()[:chips]), ("model",))


def shardings(fam, cfg: dict, mesh: Mesh) -> dict:
    return {n: NamedSharding(mesh, s[3]) for n, s in fam.specs(cfg).items()}


# ------------------------------------------------------------------ model

def _q8(x):
    """Round to float8_e4m3 under a per-tensor scale, back in float32."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, FP8_MAX / amax, 1.0)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum_fp8(spec, a, b):
    return _einsum(spec, _q8(a), _q8(b))


def _einsum_fp8_fwd(spec, a, b):
    qa, qb = _q8(a), _q8(b)
    return _einsum(spec, qa, qb), (qa, qb)


def _einsum_fp8_bwd(spec, res, ct):
    _, vjp = jax.vjp(functools.partial(_einsum, spec), *res)
    return vjp(_q8(ct))


_einsum_fp8.defvjp(_einsum_fp8_fwd, _einsum_fp8_bwd)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w)


def rope(x, theta):
    t, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, ein):
    """Causal softmax attention, a block of query rows at a time (each
    block recomputed in the backward pass) so the scores fit."""
    b, t, h, hd = q.shape
    g = h // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    blk = min(t, ATTN_BLOCK)
    if t % blk:
        raise ValueError(f"sequence {t} is not a whole number of "
                         f"{blk}-row attention blocks")

    @jax.checkpoint
    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, axis=1)
        s = ein("bthk,bshk->bhts", qi, k) * hd ** -0.5
        mask = (i * blk + jnp.arange(blk))[:, None] >= jnp.arange(t)[None]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return ein("bhts,bshk->bthk", p, v)

    out = jax.lax.map(block, jnp.arange(t // blk))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, t, h, hd)


def embed(emb, tokens, reduce):
    """Rows of the token embedding, whose table is split by rows over the
    ``model`` axis: each shard gives the rows it holds and zeros for the
    rest, and ``reduce`` sums the shards' parts."""
    i = jax.lax.axis_index("model")
    rows = emb.shape[0]
    local = tokens - i * rows
    here = (local >= 0) & (local < rows)
    return reduce(jnp.where(here[..., None],
                            emb[jnp.clip(local, 0, rows - 1)], 0.0))


def cross_entropy(z, labels):
    """Mean cross-entropy of logits ``z`` whose vocabulary columns are split
    over the ``model`` axis, against ``labels``."""
    i = jax.lax.axis_index("model")
    m = jax.lax.pmax(jax.lax.stop_gradient(jnp.max(z, axis=-1)), "model")
    lse = m + jnp.log(jax.lax.psum(
        jnp.sum(jnp.exp(z - m[..., None]), axis=-1), "model"))
    cols = z.shape[-1]
    lab = labels - i * cols
    there = (lab >= 0) & (lab < cols)
    zy = jnp.take_along_axis(z, jnp.clip(lab, 0, cols - 1)[..., None],
                             axis=-1)[..., 0]
    zy = jax.lax.psum(jnp.where(there, zy, 0.0), "model")
    return jnp.mean(lse - zy)


def grad_fn(fam, cfg: dict, mesh: Mesh, precision="f32", exchange=True):
    """jit of (float32 params, tokens [m, mb, T]) -> (mean gradient, mean
    loss) over the ``m`` microbatches, as the trainer accumulates them.
    ``exchange=False`` leaves the sums across the ``model`` axis out of the
    family's layers."""
    ein = {"f32": _einsum, "fp8": _einsum_fp8}[precision]
    pspecs = {n: s[3] for n, s in fam.specs(cfg).items()}
    reduce = (lambda x: jax.lax.psum(x, "model")) if exchange else \
        (lambda x: x)

    def body(p, tokens):
        def micro(carry, t):
            g, lsum = carry
            loss, gm = jax.value_and_grad(
                lambda q: fam.loss(cfg, q, t, ein, reduce))(p)
            return (jax.tree.map(jnp.add, g, gm), lsum + loss), None

        zeros = jax.tree.map(jnp.zeros_like, p)
        (g, lsum), _ = jax.lax.scan(micro, (zeros, jnp.zeros(())), tokens)
        m = tokens.shape[0]
        return jax.tree.map(lambda x: x / m, g), lsum / m

    sm = jax.shard_map(body, mesh=mesh, in_specs=(pspecs, P()),
                       out_specs=(pspecs, P()))
    return jax.jit(sm)


# --------------------------------------------------------------- optimizer

def lr_at(step: int, mix: dict) -> float:
    """The cosine schedule with linear warm-up and a floor of a tenth."""
    peak, warm, total = mix["peak_lr"], mix["warmup"], mix["total_steps"]
    if step < warm:
        return peak * step / max(warm, 1)
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return peak * (0.1 + 0.9 * 0.5 * (1 + np.cos(np.pi * prog)))


@functools.partial(jax.jit, static_argnums=1, donate_argnums=0)
def _clip(g, clip):
    gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    s = jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-9))
    return jax.tree.map(lambda x: x * s, g)


@jax.jit
def leaf_norms(tree):
    return {n: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for n, x in tree.items()}


@jax.jit
def diff_norms(a, b):
    return {n: jnp.sqrt(jnp.sum(jnp.square(a[n].astype(jnp.float32)
                                           - b[n].astype(jnp.float32))))
            for n in a}


def _to_bf16(x):
    """Round float32 to bfloat16 values, kept in float32.  ``reduce_precision``
    and not a cast pair: a compiler that may keep excess precision can drop
    ``astype(bfloat16).astype(float32)``, and on the TPU it does."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _adam_update(opt, decay, p, m, v, count, lr):
    bc1, bc2 = 1 - opt["b1"] ** count, 1 - opt["b2"] ** count
    out = {}
    for n in p:
        step = (m[n] / bc1) / (jnp.sqrt(v[n] / bc2) + opt["eps"])
        if n in decay:
            step = step + opt["weight_decay"] * p[n]
        out[n] = _to_bf16(p[n] - lr * step)
    return out


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=2)
def _update1(opt, decay, p, g1, lr):
    opt = dict(opt)
    m = {n: (1 - opt["b1"]) * g1[n] for n in g1}
    v = {n: (1 - opt["b2"]) * jnp.square(g1[n]) for n in g1}
    return _adam_update(opt, decay, p, m, v, 1, lr)


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=2)
def _update2(opt, decay, p, g1, g2, lr):
    opt = dict(opt)
    b1, b2 = opt["b1"], opt["b2"]
    m = {n: b1 * (1 - b1) * g1[n] + (1 - b1) * g2[n] for n in g1}
    v = {n: b2 * (1 - b2) * jnp.square(g1[n]) + (1 - b2) * jnp.square(g2[n])
         for n in g1}
    return _adam_update(opt, decay, p, m, v, 2, lr)


def _floats(tree) -> dict:
    return {n: float(x) for n, x in jax.device_get(tree).items()}


def initial_weights(fam, cfg: dict, seed: int, mesh: Mesh) -> dict:
    """The benchmark's weights for ``seed`` (``bench/weights.py``, the
    values the program starts from), as float32, in this layout."""
    from bench import weights
    w = weights.make({n: s[:3] for n, s in fam.specs(cfg).items()}, seed,
                     shardings(fam, cfg, mesh))
    return _to_f32(w)


@jax.jit
def _to_f32(tree):
    return {n: x.astype(jnp.float32) for n, x in tree.items()}


def run(fam, cfg: dict, mix: dict, seed: int, batches, chips: int, *,
        precision="f32", half=False, exchange=True) -> dict:
    """Two train steps of the family ``fam`` (a module of
    ``bench/families/``) at the sizes ``cfg``, from the weights of ``seed``
    on ``batches`` [2, m, mb, T], over the first ``chips`` devices.
    Returns each step's loss, each leaf's norm of the first gradient before
    and after clipping, and each leaf's change over the two steps.

    ``half`` is a planted fault: each step sees only its first half of the
    microbatches, and takes the mean over those."""
    mesh = make_mesh(chips)
    opt = tuple(sorted(mix["adamw"].items()))
    decay = tuple(sorted(n for n in fam.specs(cfg) if fam.is_matrix(n)))
    grads = grad_fn(fam, cfg, mesh, precision, exchange)
    rep = NamedSharding(mesh, P())
    m = batches.shape[1] // 2 if half else batches.shape[1]
    toks = [jax.device_put(np.asarray(b[:m], np.int32), rep) for b in batches]
    with jax.default_matmul_precision("highest"):
        p = initial_weights(fam, cfg, seed, mesh)
        g1, l1 = grads(p, toks[0])
        raw = _floats(leaf_norms(g1))
        g1 = _clip(g1, mix["adamw"]["clip"])
        clipped = _floats(leaf_norms(g1))
        p = _update1(opt, decay, p, g1, lr_at(0, mix))
        g2, l2 = grads(p, toks[1])
        g2 = _clip(g2, mix["adamw"]["clip"])
        p = _update2(opt, decay, p, g1, g2, lr_at(1, mix))
        del g1, g2
        change = _floats(diff_norms(p, initial_weights(fam, cfg, seed,
                                                       mesh)))
    return {"loss": [float(l1), float(l2)], "grad_raw": raw,
            "grad": clipped, "change": change}
