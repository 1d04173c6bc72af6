"""End-to-end training driver with fault tolerance and elastic re-mesh.

Runs a model on local devices -- a reduced-width demo preset (CPU smoke
scale) or ``--preset published``, the architecture at its published widths
cut only in depth -- with:
  * sharded params/optimizer via the production sharding rules,
  * async checkpointing (atomic, checksummed, keep-last-k),
  * straggler detection,
  * failure injection (--inject-failure N) exercising the full
    detect -> restore-from-checkpoint -> re-mesh -> resume path.

``--host-devices K`` splits the host CPU into K XLA devices (must be parsed
before jax initializes, hence the argv peek at the top).  ``main`` returns
the run's per-step history: ``loss``, ``gnorm`` and the per-parameter
``grad_norms``.
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
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import SHAPES, ShapeConfig, get_config, reduced_config  # noqa: E402
from repro.models import transformer as tfm  # noqa: E402
from repro.models.layers import init_param_tree, spec_tree_to_sds  # noqa: E402
from repro.runtime import obs  # noqa: E402
from repro.runtime import sharding as shd  # noqa: E402
from repro.runtime.checkpoint import CheckpointManager  # noqa: E402
from repro.runtime.elastic import adapt_config, make_plan_mesh, plan_mesh  # noqa: E402
from repro.runtime.fault import StragglerDetector, simulate_failure  # noqa: E402
from repro.runtime.optim import opt_state_specs  # noqa: E402
from repro.runtime.pipeline import DataPipeline, PipelineConfig  # noqa: E402
from repro.runtime.steps import TrainHParams, input_specs, make_train_step  # noqa: E402


def scale_config(cfg, *, d_model=256, n_layers=4, vocab=2048, heads=4):
    """Blow a reduced config up/down to a target demo scale."""
    kinds = tuple(cfg.kinds[i % cfg.n_layers] for i in range(n_layers))
    wins = tuple(cfg.layer_windows[i % cfg.n_layers] for i in range(n_layers))
    moes = tuple(cfg.layer_moe[i % cfg.n_layers] for i in range(n_layers))
    return cfg.replace(n_layers=n_layers, d_model=d_model, vocab=vocab,
                       n_heads=heads, n_kv_heads=min(cfg.n_kv_heads, heads),
                       d_head=d_model // heads, d_ff=4 * d_model,
                       dense_d_ff=4 * d_model if cfg.dense_d_ff else 0,
                       layer_kinds=kinds, windows=wins, moe_layers=moes)


def cut_depth(cfg, n_layers):
    """``cfg`` with only its first ``n_layers`` layers.  Every width, dtype
    and head count stays as published; the per-layer tuples are cut with
    the depth."""
    return cfg.replace(n_layers=n_layers,
                       layer_kinds=cfg.layer_kinds[:n_layers],
                       windows=cfg.windows[:n_layers],
                       moe_layers=cfg.moe_layers[:n_layers])


#: Demo presets give ``scale_config`` widths; ``depth`` keeps the published
#: widths and cuts the stack to that many layers.  ``peak_lr``/``warmup``
#: default to ``SCHEDULE``.
PRESETS = {
    "small": dict(d_model=256, n_layers=4, vocab=2048),    # ~5M params
    "100m": dict(d_model=768, n_layers=12, vocab=16384),   # ~110M params
    # published widths, one layer: a whole period of a homogeneous stack
    # (yi-6b: 0.70B params, 7.0 GB of bf16 weights + f32 Adam moments).
    # At these widths Adam's first, sign-like updates at the demo rate more
    # than double the loss within two steps (yi-6b on a v5e), so it warms
    # up as LLaMA-7B's recipe does (arXiv:2302.13971).
    "published": dict(depth=1, peak_lr=3e-4, warmup=2000),
}

#: (peak learning rate, warmup steps): the demo presets learn visibly in a
#: few steps
SCHEDULE = dict(peak_lr=1e-3, warmup=10)


def preset_config(arch, preset):
    """The preset's model config and its schedule ``(peak_lr, warmup)``."""
    p = {**SCHEDULE, **PRESETS[preset]}
    schedule = p.pop("peak_lr"), p.pop("warmup")
    if "depth" in p:
        return cut_depth(get_config(arch), p["depth"]), schedule
    return scale_config(reduced_config(arch), **p), schedule


def build(cfg, shape, mesh, hp):
    """Compile the train step for ``mesh`` ahead of the first call.
    Returns (compiled step, specs, shardings, compile seconds)."""
    rules = shd.make_rules(cfg, mesh, shape)
    pspecs = tfm.param_specs(cfg)
    ospecs = opt_state_specs(cfg, pspecs)
    bspecs = input_specs(cfg, shape)
    p_sh = shd.spec_shardings(pspecs, mesh, rules)
    o_sh = shd.spec_shardings(ospecs, mesh, rules)
    b_sh = shd.spec_shardings(bspecs, mesh, rules)
    rep = NamedSharding(mesh, P())
    fn = make_train_step(cfg, hp, shard_ctx=(mesh, rules))
    step_fn = jax.jit(fn, in_shardings=(p_sh, o_sh, b_sh, rep),
                      out_shardings=(p_sh, o_sh, None),
                      donate_argnums=(0, 1))
    t0 = time.perf_counter()
    compiled = step_fn.lower(
        spec_tree_to_sds(pspecs), spec_tree_to_sds(ospecs),
        spec_tree_to_sds(bspecs), jax.ShapeDtypeStruct((), jnp.int32)).compile()
    compile_s = time.perf_counter() - t0
    obs.note_program(compiled)
    return compiled, (pspecs, ospecs), (p_sh, o_sh, b_sh), compile_s


def init_params(pspecs, seed, shardings):
    """Random weights from ``seed``, each leaf made where ``shardings``
    places it (a pytree prefix): no device holds a whole sharded leaf."""
    return jax.jit(lambda key: init_param_tree(pspecs, key),
                   out_shardings=shardings)(jax.random.PRNGKey(seed))


def init_state(cfg, specs, shardings, seed):
    pspecs, ospecs = specs
    p_sh, o_sh, _ = shardings
    params = init_params(pspecs, seed, p_sh)
    opt = init_params(ospecs, 0, o_sh)                     # zeros
    return params, opt


def peak_bytes_in_use():
    """Largest ``peak_bytes_in_use`` over local devices, or None where the
    backend keeps no memory stats."""
    stats = [d.memory_stats() for d in jax.local_devices()]
    peaks = [s["peak_bytes_in_use"] for s in stats
             if s and "peak_bytes_in_use" in s]
    return max(peaks) if peaks else None


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--preset", default="small", choices=sorted(PRESETS))
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--ckpt-dir", default="artifacts/ckpt_demo")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--inject-failure", type=int, default=-1)
    ap.add_argument("--host-devices", type=int, default=0)  # consumed above
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--peak-lr", type=float, default=None,
                    help="peak learning rate (default: the preset's)")
    ap.add_argument("--warmup", type=int, default=None,
                    help="linear warmup steps (default: the preset's)")
    ap.add_argument("--devices", type=int, default=0,
                    help="train on the first N local devices (default: all)")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)

    cfg, (peak_lr, warmup) = preset_config(args.arch, args.preset)
    cfg = cfg.replace(train_microbatches=args.microbatches)
    shape = ShapeConfig("demo", "train", args.seq, args.global_batch)
    hp = TrainHParams(
        peak_lr=peak_lr if args.peak_lr is None else args.peak_lr,
        warmup=warmup if args.warmup is None else args.warmup,
        total_steps=args.steps)

    n_dev = args.devices or len(jax.devices())
    plan = plan_mesh(n_dev, args.global_batch, prefer_model=min(4, n_dev),
                     microbatches=cfg.train_microbatches)
    mesh = make_plan_mesh(plan)
    cfg = adapt_config(cfg, plan, args.global_batch)
    print(f"[train] arch={cfg.name} preset={args.preset} "
          f"params={cfg.n_params()/1e6:.1f}M layers={cfg.n_layers} "
          f"d_model={cfg.d_model} heads={cfg.n_heads}/{cfg.n_kv_heads} "
          f"d_head={cfg.head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab} "
          f"dtype={cfg.param_dtype} seq={args.seq} "
          f"global_batch={args.global_batch} "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"microbatches={cfg.train_microbatches}")

    step_fn, specs, shardings, compile_s = build(cfg, shape, mesh, hp)
    mem = step_fn.memory_analysis()
    if mem is not None:
        print(f"[train] step program per device: arguments "
              f"{mem.argument_size_in_bytes} B, temporaries "
              f"{mem.temp_size_in_bytes} B; compiled in {compile_s:.2f} s")
    ckpt = CheckpointManager(args.ckpt_dir, keep=3)
    pipe = DataPipeline(cfg, shape, PipelineConfig(seed=args.seed),
                        sharding=shardings[2]).start()

    start_step = 0
    if args.resume and ckpt.all_steps():
        tree = {"params": spec_tree_to_sds(specs[0]),
                "opt": spec_tree_to_sds(specs[1])}
        sh = {"params": shardings[0], "opt": shardings[1]}
        restored, manifest = ckpt.restore_latest(tree, shardings=sh)
        params, opt = restored["params"], restored["opt"]
        start_step = manifest["step"]
        pipe.restore(manifest["extra"]["pipeline"])
        print(f"[train] resumed from step {start_step}")
    else:
        params, opt = init_state(cfg, specs, shardings, args.seed)

    detector = StragglerDetector()
    history = {"loss": [], "gnorm": [], "grad_norms": []}
    step_s, ckpt_s = [], 0.0
    failure_schedule = ({args.inject_failure: ("device_loss", {"lost": 1})}
                        if args.inject_failure >= 0 else {})

    step = start_step
    while step < args.steps:
        ev = simulate_failure(step, failure_schedule)
        if ev is not None:
            print(f"[fault] injected {ev.kind} at step {step}: "
                  "restoring from checkpoint onto reduced mesh")
            ckpt.wait()
            n_healthy = max(1, n_dev - ev.payload["lost"])
            plan = plan_mesh(n_healthy, args.global_batch,
                             prefer_model=min(4, n_healthy),
                             microbatches=cfg.train_microbatches)
            mesh = make_plan_mesh(plan)
            cfg = adapt_config(cfg, plan, args.global_batch)
            step_fn, specs, shardings, dt = build(cfg, shape, mesh, hp)
            compile_s += dt
            pipe.sharding = shardings[2]
            tree = {"params": spec_tree_to_sds(specs[0]),
                    "opt": spec_tree_to_sds(specs[1])}
            sh = {"params": shardings[0], "opt": shardings[1]}
            restored, manifest = ckpt.restore_latest(tree, shardings=sh,
                                                     max_step=step)
            params, opt = restored["params"], restored["opt"]
            step = manifest["step"]
            pipe.restore(manifest["extra"]["pipeline"])
            failure_schedule.pop(ev.step, None)
            print(f"[fault] resumed at step {step} on {plan.size} device(s)")
            continue

        batch = next(pipe)
        t0 = time.perf_counter()
        params, opt, metrics = step_fn(params, opt, batch,
                                       jnp.asarray(step, jnp.int32))
        loss = float(metrics["loss"])
        dt = time.perf_counter() - t0
        verdict = detector.record(dt)
        history["loss"].append(loss)
        history["gnorm"].append(float(metrics["gnorm"]))
        history["grad_norms"].append(jax.tree.map(float,
                                                  metrics["grad_norms"]))
        step_s.append(dt)
        step += 1
        if not args.quiet and (step % 5 == 0 or step == 1):
            print(f"  step {step:4d} loss={loss:.4f} {dt*1e3:7.1f}ms "
                  f"gnorm={history['gnorm'][-1]:.2f} [{verdict}]")
        if step % args.ckpt_every == 0 or step == args.steps:
            t0 = time.perf_counter()
            ckpt.save(step, {"params": params, "opt": opt},
                      extra={"pipeline": pipe.state()})
            ckpt_s += time.perf_counter() - t0
    t0 = time.perf_counter()
    ckpt.wait()
    ckpt_s += time.perf_counter() - t0
    pipe.stop()

    losses = history["loss"]
    first, last = np.mean(losses[:5]), np.mean(losses[-5:])
    print(f"[train] done: loss {first:.4f} -> {last:.4f} "
          f"({'improved' if last < first else 'NOT improved'})")
    print(f"[train] compile_s={compile_s:.3f} ckpt_s={ckpt_s:.3f} "
          f"peak_bytes_in_use={peak_bytes_in_use()} "
          f"step_s={[round(t, 4) for t in step_s]}")
    return history


if __name__ == "__main__":   # deprecated spelling; kept as a shim
    import sys as _sys
    print("note: `python -m repro.launch.train` is now "
          "`python -m repro train`", file=_sys.stderr)
    main()
