"""Smoke run of the trainer and the kernel tuner's measured path on a TPU.

    python chip_smoke.py                # one chip: kernels, then training
    python chip_smoke.py --four-chips   # tensor-parallel training on 4 chips

Everything runs in this one process, through the entry points a user calls:
``repro.core.kerneltune.measure_case`` with ``WallClockBackend`` and
``repro.launch.train.main``.  Data and weights are made from ``--seed``.

Phases (any failure exits non-zero):

* device  -- name the device JAX found; anything but a TPU is an error.
* kernels -- one yi-6b GEMM case and the yi-6b flash case, each measured
  over its roofline-seeded tiles with every tile verified against the jnp
  reference, and the kernels shown to compile (``tpu_custom_call``).
* train   -- yi-6b at its published widths (``--preset published``: one
  layer) for a few steps with a checkpoint, at a learning rate that moves
  the bf16 weights from the first update on.  Checked against a plain
  float32 reference of the same weights and batches on one device: the
  first step's loss and each parameter's gradient norm, and the second
  step's loss after the first update.

``--four-chips`` runs only the train phase: on the planned (1, 4) mesh and
then on one chip, each checked against the reference, and the loss of
every step on 4 chips against the same step on one.

The last line of stdout is one JSON object naming the device.
"""
import argparse
import gc
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "yi-6b"
PRESET = "published"
SEQ, GLOBAL_BATCH, MICROBATCHES, STEPS = 2048, 8, 8, 6
# Peak learning rate, with no warmup, so that the first update moves the
# bf16 weights.  An AdamW first step is ~lr * sign(grad); 1e-5 is over half
# an ulp of every bf16 weight under 2**-8 in magnitude, which at init is
# about two thirds of yi-6b's embedding and a fifth to a third of each of
# its other matrices.  The cosine decay over the run keeps moving them.
LR = 1e-5
# |first-step loss - float32 reference|.  bf16 rounding of activations and
# logits is zero-mean, and the loss averages it over the step's 16384
# tokens: the expected gap is ~1e-4.  Dropping the attention output moves
# the loss at init by ~1e-2 (measured at reduced width on the CPU).
LOSS_TOL = 1e-3
# Relative gap of each parameter's first-step gradient norm to the float32
# one.  bf16 rounding leaves ~0.5% (yi-6b widths cut to 256 on the CPU); a
# lost or doubled all-reduce across 4 chips moves a norm by 2x or more, and
# a bf16 scatter-add of the embedding gradient left it 12% short.
GRAD_TOL = 3e-2
# The second step's loss, after one update of LR, against the float32
# reference's update: within LOSS_TOL plus this share of how far that
# update moved the loss.  The update is sign-like, so each gradient entry
# whose sign bf16 rounding flips moves its weight the wrong way; at yi-6b
# widths cut to 256 on the CPU the gap was under 1% of the move.  The same
# bound holds each step's loss on 4 chips to the one on 1 chip, against
# how far training had moved the loss from the initial weights' by then.
MOVE_TOL = 5e-2


def device_phase(want: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"[device] platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found platform "
                 f"{d.platform!r} ({d.device_kind})")
    if len(devs) < want:
        sys.exit(f"chip_smoke: needs {want} chips, JAX found {len(devs)}")
    return d


def kernel_phase(seed: int, dev) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.configs.workloads import flash_case, gemm_cases
    from repro.core.kerneltune import bucket_case, measure_case, seed_tiles
    from repro.data.logstore import LogStore
    from repro.kernels import ops
    from repro.kernels.timing import WallClockBackend

    cfg = get_config(ARCH)
    gemm = next(c for c in gemm_cases(cfg, "train_4k", arch_id=ARCH)
                if c.label.endswith("/ffn_up"))
    cases = [gemm, flash_case(cfg, "train_4k", arch_id=ARCH)]
    with tempfile.TemporaryDirectory() as tmp:
        store = LogStore(Path(tmp) / "tune_store.jsonl")
        for case in cases:
            bcase = bucket_case(case)
            tiles = seed_tiles(bcase)
            backend = WallClockBackend(verify=True, seed=seed)
            t0 = time.perf_counter()
            records, stats = measure_case(case, backend, store, tiles=tiles)
            wall = time.perf_counter() - t0
            print(f"[kernels] {case.label} bucket=({bcase.m},{bcase.k},"
                  f"{bcase.n}) heads={bcase.heads} tiles={len(tiles)} "
                  f"stats={stats} verified={backend.verified} "
                  f"verify_failures={backend.verify_failures} "
                  f"on {backend.platform}/{backend.device_kind} "
                  f"wall={wall:.2f}s", flush=True)
            for r in records:
                print(f"[kernels]   tile=({r.p_r},{r.p_c}"
                      f"{',' + str(r.meta['bk']) if 'bk' in r.meta else ''})"
                      f" median_s={r.time_s!r} (host clock, observation)")
            if (backend.platform, backend.device_kind) != \
                    (dev.platform, dev.device_kind):
                raise RuntimeError(
                    f"timed on {backend.platform}/{backend.device_kind}, "
                    f"expected {dev.platform}/{dev.device_kind}")
            if stats["measured"] != len(tiles) or stats["pruned"]:
                raise RuntimeError(f"not every seeded tile was measured: "
                                   f"{stats}")
            if backend.verify_failures or backend.verified != len(tiles):
                raise RuntimeError(
                    f"{backend.verify_failures} tile(s) disagree with the "
                    f"jnp reference; {backend.verified}/{len(tiles)} verified")
            if not all(math.isfinite(r.time_s) for r in records):
                raise RuntimeError("a measured tile has no finite time")

            # compiled, not interpreted: the Mosaic kernel is in the program
            t, dt = tiles[0], jnp.dtype(bcase.dtype)
            if case.kernel == "flash":
                q = jax.ShapeDtypeStruct(
                    (bcase.batch, bcase.m, bcase.heads, bcase.k), dt)
                kv = jax.ShapeDtypeStruct(
                    (bcase.batch, bcase.n, bcase.heads, bcase.k), dt)
                text = ops.flash_attention.lower(
                    q, kv, kv, causal=bcase.causal, block_q=t[0],
                    block_k=t[1]).as_text()
            else:
                a = jax.ShapeDtypeStruct((bcase.m, bcase.k), dt)
                b = jax.ShapeDtypeStruct((bcase.k, bcase.n), dt)
                text = ops.matmul.lower(a, b, block_m=t[0], block_n=t[1],
                                        block_k=t[2]).as_text()
            if "tpu_custom_call" not in text:
                raise RuntimeError(f"{case.label}: tile {t} lowered without "
                                   "a tpu_custom_call (interpret mode?)")
            print(f"[kernels] {case.label}: tile {t} lowers to "
                  "tpu_custom_call", flush=True)


def flat_norms(tree) -> dict:
    import jax
    return {jax.tree_util.keystr(k): float(v)
            for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def reference(cfg, seed: int, dev) -> dict:
    """Plain float32 on one device, no kernels, highest matmul precision.

    The step's weights (``init_params`` from the same seed) cast to f32:
    their loss on each of the run's batches (``initial``, the mean over
    microbatches as the train step takes it); the first step's gradient
    norms, each parameter's and the global one; and the loss on the second
    batch after the first AdamW update at ``LR`` (``updated``).  That
    update is written out: at count 1 the bias-corrected moments make it
    ``g / (|g| + eps)`` of the clipped gradient, plus weight decay on
    matrices, rounded to the weights' dtype."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from repro.configs import ShapeConfig
    from repro.launch import train
    from repro.models import transformer as tfm
    from repro.runtime.optim import AdamWConfig
    from repro.runtime.pipeline import DataPipeline, PipelineConfig

    shape = ShapeConfig("smoke", "train", SEQ, GLOBAL_BATCH)
    params = train.init_params(tfm.param_specs(cfg), seed,
                               SingleDeviceSharding(dev))
    pipe = DataPipeline(cfg, shape, PipelineConfig(seed=seed))
    batches = [jax.device_put(next(pipe)["tokens"], dev)
               for _ in range(STEPS)]
    f32 = jax.jit(lambda p: jax.tree.map(lambda x: x.astype(jnp.float32), p))

    def loss(p32, tokens):
        return tfm.train_loss(cfg, p32, {"tokens": tokens})[0]

    @jax.jit
    def mean_loss(p32, tokens):
        return jnp.mean(jax.lax.map(lambda t: loss(p32, t), tokens))

    value_and_grad = jax.jit(jax.value_and_grad(loss))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=0)
    opt = AdamWConfig()

    @jax.jit
    def first_update(params, g):
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, opt.clip / gnorm)

        def upd(p, g):
            g = g * scale
            step = g / (jnp.abs(g) + opt.eps)
            if p.ndim >= 2:
                step = step + opt.weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - LR * step).astype(p.dtype)
        return jax.tree.map(upd, params, g)

    with jax.default_matmul_precision("highest"):
        p32 = f32(params)
        initial = [float(mean_loss(p32, b)) for b in batches]
        g = None
        for tokens in batches[0]:           # one microbatch at a time
            gm = value_and_grad(p32, tokens)[1]
            g = gm if g is None else add(g, gm)
        del p32, gm
        g = jax.tree.map(lambda x: x / len(batches[0]), g)
        norms = flat_norms(jax.tree.map(
            lambda x: jnp.sqrt(jnp.sum(x * x)), g))
        params = first_update(params, g)
        del g
        updated = float(mean_loss(f32(params), batches[1]))
    return {"initial": initial, "updated": updated,
            "gnorm": sum(v * v for v in norms.values()) ** 0.5,
            "grad_norms": norms}


def run_train(seed: int, devices: int) -> dict:
    """``train.main`` at the published widths on the first ``devices``
    chips, for ``STEPS`` steps, with a checkpoint at the end."""
    from repro.launch import train

    with tempfile.TemporaryDirectory() as ckpt_dir:
        print(f"[train] checkpoint dir free bytes="
              f"{shutil.disk_usage(ckpt_dir).free}", flush=True)
        hist = train.main([
            "--arch", ARCH, "--preset", PRESET, "--steps", str(STEPS),
            "--seq", str(SEQ), "--global-batch", str(GLOBAL_BATCH),
            "--microbatches", str(MICROBATCHES), "--ckpt-dir", ckpt_dir,
            "--ckpt-every", str(STEPS), "--seed", str(seed),
            "--peak-lr", str(LR), "--warmup", "0",
            "--devices", str(devices)])
    gc.collect()                     # the run's weights leave the device
    print(f"[train] {devices} chip(s): losses={hist['loss']} "
          f"gnorms={hist['gnorm']}", flush=True)
    if len(hist["loss"]) != STEPS or not all(
            math.isfinite(x) for x in hist["loss"] + hist["gnorm"]):
        raise RuntimeError(f"expected {STEPS} finite losses and gradient "
                           f"norms, got {hist}")
    return hist


def check(what: str, gap: float, tol: float) -> None:
    print(f"[train] {what}: gap={gap!r} tol={tol}", flush=True)
    if not gap <= tol:
        raise RuntimeError(f"{what}: {gap} is over the tolerance {tol}")


def check_against_reference(hist: dict, ref: dict, label: str) -> None:
    check(f"{label} step 1 loss {hist['loss'][0]!r} vs float32 "
          f"{ref['initial'][0]!r}", abs(hist["loss"][0] - ref["initial"][0]),
          LOSS_TOL)
    moved = ref["updated"] - ref["initial"][1]
    check(f"{label} step 2 loss {hist['loss'][1]!r}, after one update, vs "
          f"float32 {ref['updated']!r} (the update moved it {moved!r})",
          abs(hist["loss"][1] - ref["updated"]),
          LOSS_TOL + MOVE_TOL * abs(moved))
    got = flat_norms(hist["grad_norms"][0])
    rel = {k: abs(got[k] - v) / v for k, v in ref["grad_norms"].items()}
    worst = max(rel, key=rel.get)
    print(f"[train] {label} step 1 gradient norm {hist['gnorm'][0]!r} vs "
          f"float32 {ref['gnorm']!r}", flush=True)
    check(f"{label} step 1 gradient norm of {worst}, the worst of "
          f"{len(rel)} parameters, relative", rel[worst], GRAD_TOL)


def train_phase(seed: int, dev, four_chips: bool) -> None:
    from repro.launch import train

    cfg = train.preset_config(ARCH, PRESET)[0].replace(
        train_microbatches=MICROBATCHES)
    runs = {n: run_train(seed, n) for n in ((4, 1) if four_chips else (1,))}
    ref = reference(cfg, seed, dev)
    print(f"[train] float32 reference: initial weights' losses "
          f"{ref['initial']}", flush=True)
    for n, hist in runs.items():
        check_against_reference(hist, ref, f"{n} chip(s)")
    if four_chips:
        for k, (a, b, start) in enumerate(zip(
                runs[4]["loss"], runs[1]["loss"], ref["initial"])):
            check(f"step {k + 1} loss on 4 chips {a!r} vs 1 chip {b!r} "
                  f"(training moved it {b - start!r})", abs(a - b),
                  LOSS_TOL + MOVE_TOL * abs(b - start))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="train on the planned 4-chip mesh only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from repro.launch.__main__ import use_compile_cache

    dev = device_phase(4 if args.four_chips else 1)
    print(f"[device] compile cache: {use_compile_cache()}", flush=True)
    if not args.four_chips:
        kernel_phase(args.seed, dev)
        gc.collect()
    train_phase(args.seed, dev, args.four_chips)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
