"""Batched serving driver: prefill a prompt batch, decode with KV caches.

Demonstrates the serving path end-to-end on CPU at reduced scale: ring
caches for sliding-window layers, latent caches for MLA, SSM states for
mamba/hymba -- the same code the decode_32k / long_500k dry-run cells lower.
"""
import os
import sys

if "--host-devices" in sys.argv:                      # must precede jax init
    _n = sys.argv[sys.argv.index("--host-devices") + 1]
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --xla_force_host_platform_device_count={_n}")

import argparse       # noqa: E402
import time           # noqa: E402

import jax            # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np    # noqa: E402

from repro.launch.train import PRESETS, preset_config  # noqa: E402
from repro.models import transformer as tfm  # noqa: E402
from repro.models.layers import init_param_tree  # noqa: E402


def sample(logits, key, temperature):
    if temperature <= 0:
        return jnp.argmax(logits, axis=-1)
    return jax.random.categorical(key, logits / temperature, axis=-1)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--preset", default="small", choices=sorted(PRESETS))
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--host-devices", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg, _ = preset_config(args.arch, args.preset)
    params = init_param_tree(tfm.param_specs(cfg), jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)
    shape = ((args.batch, cfg.n_codebooks, args.prompt_len)
             if cfg.n_codebooks > 1 else (args.batch, args.prompt_len))
    prompts = jnp.asarray(rng.integers(2, cfg.vocab, shape), jnp.int32)
    img = None
    if cfg.frontend == "vision":
        img = jnp.asarray(rng.normal(0, 0.02,
                                     (args.batch, cfg.image_tokens, cfg.d_model)),
                          jnp.float32)

    capacity = (args.prompt_len + args.gen_len + cfg.meta_tokens
                + (cfg.image_tokens if img is not None else 0) + 1)

    prefill = jax.jit(lambda p, t: tfm.prefill(cfg, p, t, img))
    decode = jax.jit(lambda p, c, t: tfm.decode_step(cfg, p, c, t),
                     donate_argnums=(1,))

    t0 = time.time()
    last_logits, cache = prefill(params, prompts)
    cache = tfm.grow_cache(cfg, cache, capacity)
    jax.block_until_ready(last_logits)
    t_prefill = time.time() - t0

    key = jax.random.PRNGKey(args.seed)
    tok = sample(last_logits[:, -1], key, args.temperature)
    generated = [tok]
    t0 = time.time()
    for i in range(args.gen_len - 1):
        key, sub = jax.random.split(key)
        new = tok[:, None] if cfg.n_codebooks == 1 else \
            tok.reshape(args.batch, cfg.n_codebooks, 1)
        logits, cache = decode(params, cache, new)
        tok = sample(logits[:, -1] if cfg.n_codebooks == 1 else
                     logits[:, 0, :, :].reshape(args.batch * cfg.n_codebooks, -1),
                     sub, args.temperature)
        if cfg.n_codebooks > 1:
            tok = tok.reshape(args.batch, cfg.n_codebooks)
        generated.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0

    n_new = args.gen_len * args.batch
    print(f"[serve] arch={cfg.name} batch={args.batch} "
          f"prefill={t_prefill*1e3:.1f}ms "
          f"decode={t_decode/max(args.gen_len-1,1)*1e3:.2f}ms/step "
          f"throughput={n_new/max(t_decode,1e-9):.1f} tok/s")
    out = jnp.stack([g if g.ndim == 1 else g[:, 0] for g in generated], axis=1)
    assert out.shape == (args.batch, args.gen_len)
    assert bool(jnp.all(out >= 0)) and bool(jnp.all(out < cfg.vocab))
    print("[serve] sample row:", np.asarray(out[0])[:16].tolist())
    return out


if __name__ == "__main__":   # deprecated spelling; kept as a shim
    import sys as _sys
    print("note: `python -m repro.launch.serve` is now "
          "`python -m repro serve`", file=_sys.stderr)
    main()
