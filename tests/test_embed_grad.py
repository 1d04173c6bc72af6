"""The train step's token-embedding gradient.

With a float32 accumulator and several microbatches, ``make_train_step``
scatter-adds the gradient of the gathered rows into the accumulator that
the microbatch scan carries; with a bfloat16 accumulator or one microbatch
it takes the table's dense gradient through ``gather_rows``.  Each is held
to a step written out here: the row scatter to one that differentiates
through a plain float32 ``table[ids]`` gather, the dense paths bit for bit
to the accumulation they always had."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models import transformer as tf
from repro.models.layers import init_param_tree
from repro.runtime.optim import cosine_schedule, opt_state_specs, opt_update
from repro.runtime.steps import TrainHParams, make_train_step

HP = TrainHParams(peak_lr=1e-3, warmup=0, total_steps=1000)
REPEATED_ID = 7

CASES = {
    "untied": ("yi-6b", {}),
    "tied": ("yi-6b", {"tie_embeddings": True}),
    "codebooks": ("musicgen-large", {}),
    "bf16_accumulator": ("yi-6b", {"grad_accum_dtype": "bfloat16"}),
    "one_microbatch": ("yi-6b", {"train_microbatches": 1}),
}
DENSE = ("bf16_accumulator", "one_microbatch")


def make_cfg(case):
    arch, kw = CASES[case]
    kw = {"train_microbatches": 4, **kw}
    return reduced_config(arch).replace(param_dtype="bfloat16",
                                        compute_dtype="bfloat16", **kw)


def make_state(cfg, m, b=2, t=512, seed=0):
    """Weights, zero AdamW state and ``m`` microbatches in which one id
    fills nine tenths of the tokens: thousands of repeats per step."""
    ps = tf.param_specs(cfg)
    params = init_param_tree(ps, jax.random.PRNGKey(seed))
    opt = init_param_tree(opt_state_specs(cfg, ps), jax.random.PRNGKey(1))
    rng = np.random.default_rng(seed)
    shape = (m, b, cfg.n_codebooks, t) if cfg.n_codebooks > 1 else (m, b, t)
    tokens = np.where(rng.random(shape) < 0.9, REPEATED_ID,
                      rng.integers(0, cfg.vocab, shape))
    return params, opt, {"tokens": jnp.asarray(tokens, jnp.int32)}


def finish(cfg, params, opt, grads, lsum, n, step):
    """What the step does after its accumulation."""
    lr = cosine_schedule(step, peak_lr=HP.peak_lr, warmup=HP.warmup,
                         total=HP.total_steps)
    grads = jax.tree.map(lambda g: g / n, grads)
    norms = jax.tree.map(
        lambda g: jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32)))), grads)
    new_p, new_o, _ = opt_update(cfg, grads, opt, params, lr)
    return new_p, new_o, {"loss": lsum / n, "grad_norms": norms}


def dense_step(cfg):
    """The dense accumulation: the whole gradient of each microbatch, the
    table's through ``gather_rows``, added in ``cfg.grad_accum_dtype``."""
    acc_dt = jnp.dtype(cfg.grad_accum_dtype)

    def step(params, opt, batch, step):
        def micro(mb):
            (loss, _), g = jax.value_and_grad(
                lambda p: tf.train_loss(cfg, p, mb), has_aux=True)(params)
            return loss, g
        if cfg.train_microbatches == 1:
            loss, grads = micro(jax.tree.map(lambda x: x[0], batch))
            lr = cosine_schedule(step, peak_lr=HP.peak_lr, warmup=HP.warmup,
                                 total=HP.total_steps)
            norms = jax.tree.map(lambda g: jnp.sqrt(jnp.sum(jnp.square(
                g.astype(jnp.float32)))), grads)
            new_p, new_o, _ = opt_update(cfg, grads, opt, params, lr)
            return new_p, new_o, {"loss": loss, "grad_norms": norms}

        def body(carry, mb):
            gacc, lsum = carry
            loss, g = micro(mb)
            return (jax.tree.map(lambda a, b: a + b.astype(acc_dt), gacc, g),
                    lsum + loss), ()
        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, acc_dt), params)
        (grads, lsum), _ = jax.lax.scan(
            body, (zeros, jnp.zeros((), jnp.float32)), batch)
        return finish(cfg, params, opt, grads, lsum, cfg.train_microbatches,
                      step)
    return step


def gather_step(cfg):
    """The table's gradient taken densely in float32 through a plain
    ``table[ids]`` gather (and, if tied, the head), summed over the
    microbatches in float32 with the other leaves'."""
    def step(params, opt, batch, step):
        table = params["tok_emb"]
        rest = {k: v for k, v in params.items() if k != "tok_emb"}

        def loss_fn(rest, t32, mb):
            rows = t32[tf.embedding_index(cfg, mb["tokens"])]
            p = {**rest, "tok_emb": t32.astype(table.dtype)}
            return tf.train_loss(cfg, p, mb, rows=rows.astype(table.dtype))
        grads, lsum = None, 0.0
        for i in range(cfg.train_microbatches):
            mb = jax.tree.map(lambda x: x[i], batch)
            (loss, _), (g, gt) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True)(
                    rest, table.astype(jnp.float32), mb)
            g = jax.tree.map(lambda x: x.astype(jnp.float32),
                             {**g, "tok_emb": gt})
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
            lsum = lsum + loss
        return finish(cfg, params, opt, grads, lsum, cfg.train_microbatches,
                      step)
    return step


def as_np(tree):
    return jax.tree.map(lambda x: np.asarray(x, np.float32), tree)


@pytest.mark.parametrize("case", list(CASES))
def test_train_step_embedding_gradient(case):
    cfg = make_cfg(case)
    params, opt, batch = make_state(cfg, cfg.train_microbatches)
    step = jnp.asarray(0)
    got = as_np(jax.jit(make_train_step(cfg, HP))(params, opt, batch, step))
    ref_fn = dense_step(cfg) if case in DENSE else gather_step(cfg)
    want = as_np(jax.jit(ref_fn)(params, opt, batch, step))
    (gp, go, gm), (wp, wo, wm) = got, want
    if case in DENSE:                       # the path it always took
        for g, w in ((gp, wp), (go, wo), (gm["grad_norms"], wm["grad_norms"]),
                     (gm["loss"], wm["loss"])):
            jax.tree.map(np.testing.assert_array_equal, g, w)
        return
    np.testing.assert_allclose(gm["loss"], wm["loss"], rtol=1e-6)
    jax.tree.map(lambda g, w: np.testing.assert_allclose(g, w, rtol=1e-4),
                 gm["grad_norms"], wm["grad_norms"])
    # the first moment is a tenth of each (clipped) gradient, element-wise
    jax.tree.map(lambda g, w: np.testing.assert_allclose(
        g, w, rtol=1e-4, atol=1e-6 * np.abs(w).max()), go["mu"], wo["mu"])
    # a bf16 weight may round the other way: one ulp is 2**-8 of it
    jax.tree.map(lambda g, w: np.testing.assert_allclose(
        g, w, rtol=2 ** -7, atol=1e-8), gp, wp)
    assert np.abs(wo["mu"]["tok_emb"]).max() > 0


# --- compiled text ---------------------------------------------------------

def computations(text):
    """``{name: [instruction lines]}`` of an HLO module's text."""
    comps, name = {}, None
    for line in text.splitlines():
        if name is None:
            if line and not line[0].isspace() and line.rstrip().endswith("{"):
                name = line.split()[1 if line.startswith("ENTRY") else 0]
                name = name.lstrip("%")
                comps[name] = []
        elif line.strip() == "}":
            name = None
        else:
            comps[name].append(line)
    return comps


def loop_ops(text, shape):
    """Opcodes of the instructions of result type ``shape`` in the body of
    the loop that carries a ``shape`` accumulator, and in every computation
    that body calls."""
    comps = computations(text)
    entry = next(n for n in comps if n.startswith("main"))
    body = next(re.search(r"body=%?([\w.\-]+)", ln).group(1)
                for ln in comps[entry] if " while(" in ln and shape in ln)
    seen, todo = set(), [body]
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for ln in comps[c]:
            todo += [n for n in re.findall(r"%([\w.\-]+)", ln) if n in comps]
    pat = re.compile(r"=\s*" + re.escape(shape) + r"(?:\{[^}]*\})?\s+([\w-]+)\(")
    return [m.group(1) for c in seen for ln in comps[c]
            for m in [pat.search(ln)] if m]


@pytest.mark.parametrize("case", ["untied", "codebooks"])
def test_microbatch_loop_scatters_rows_into_the_accumulator(case):
    cfg = make_cfg(case)
    params, opt, batch = make_state(cfg, cfg.train_microbatches, t=32)
    text = jax.jit(make_train_step(cfg, HP)).lower(
        params, opt, batch, jnp.asarray(0)).compile().as_text()
    shape = "f32[" + ",".join(map(str, params["tok_emb"].shape)) + "]"
    ops = loop_ops(text, shape)
    assert ops.count("scatter") == 1, ops
    assert not {"broadcast", "copy", "add"} & set(ops), ops
