"""§Perf hillclimbing harness.

For each chosen cell, lowers a sequence of named VARIANTS (sharding layout,
mesh factorization, microbatch count, remat policy, MoE dispatch mode,
cache sharding, gradient compression) against real XLA compilations at
512-host-device scale, and reports per variant:

  * the analytic three-term roofline (variant-matched config),
  * XLA-parsed collective bytes (body-once; *relative* deltas are exact
    because loop structure is identical across variants),
  * per-device memory (args + temp, with the f32-probe TPU estimate),
  * compile time.

Run inside a fresh process (needs 512 host devices):
    PYTHONPATH=src python -m benchmarks.perf --cell dsv3_train
Writes artifacts/perf/<cell>.json consumed by EXPERIMENTS.md §Perf.
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")

import argparse   # noqa: E402
import json       # noqa: E402
from pathlib import Path  # noqa: E402

from repro.configs import SHAPES, get_config  # noqa: E402
from repro.core.roofline import cell_roofline  # noqa: E402
from repro.launch.dryrun import run_cell       # noqa: E402
from repro.launch.mesh import make_mesh        # noqa: E402

ART = Path(__file__).resolve().parent.parent / "artifacts" / "perf"

# variant fields: mesh (shape, axes) | microbatches | cfg_overrides | note
CELLS = {
    # 1) most representative of the paper's technique: the EP-MoE monster.
    "dsv3_train": {
        "arch": "deepseek-v3-671b", "shape": "train_4k",
        "variants": [
            ("baseline_16x16_mb16", dict()),
            ("mb32", dict(microbatches=32)),
            ("dp8_tp32_mb32", dict(mesh=((8, 32), ("data", "model")),
                                   microbatches=32)),
            ("dp32_tp8_mb32", dict(mesh=((32, 8), ("data", "model")),
                                   microbatches=32)),
            # 512 chips with the FSDP shards spanning the pod axis (DCN
            # all-gathers, halved per-device state)
            ("pods_fsdp_dcn_mb32", dict(
                mesh=((2, 16, 16), ("pod", "data", "model")),
                microbatches=32,
                rules={"embed": ("data", "pod"),
                       "embed_out": ("data", "pod")})),
            ("mb32_remat_dots", dict(
                microbatches=32, cfg_overrides={"remat_policy": "dots"})),
            # FSDP traffic scales with microbatch count x remat re-forward:
            # fewest microbatches that fit + dots remat = fewest re-gathers
            ("mb16_remat_dots", dict(
                microbatches=16, cfg_overrides={"remat_policy": "dots"})),
            # gather-minimizing mb only fits with 512 chips of residency
            ("pods512_dp32_tp16_mb4", dict(
                mesh=((2, 16, 16), ("pod", "data", "model")),
                microbatches=4)),
        ],
    },
    # 2) worst roofline fraction among dense trainers: collective-bound TP.
    "yi_train": {
        "arch": "yi-6b", "shape": "train_4k",
        "variants": [
            ("baseline_16x16_mb8", dict()),
            ("dp64_tp4", dict(mesh=((64, 4), ("data", "model")))),
            ("dp256_tp1_fsdp", dict(mesh=((256, 1), ("data", "model")),
                                    cfg_overrides={"param_sharding": "fsdp"})),
            ("dp64_tp4_mb4", dict(mesh=((64, 4), ("data", "model")),
                                  microbatches=4)),
            ("dp64_tp4_mb4_dots", dict(
                mesh=((64, 4), ("data", "model")), microbatches=4,
                cfg_overrides={"remat_policy": "dots"})),
            # more microbatches amortize nothing here but shrink live
            # activations -- the memory-fitting variant of the dots winner
            ("dp64_tp4_mb16_dots", dict(
                mesh=((64, 4), ("data", "model")), microbatches=16,
                cfg_overrides={"remat_policy": "dots"})),
            # ZeRO-1: fp32 Adam state (12.1 GiB at tp=4) shards over data;
            # bf16 grad accumulation halves the accumulator
            ("dp64_tp4_mb4_dots_zero1", dict(
                mesh=((64, 4), ("data", "model")), microbatches=4,
                cfg_overrides={"remat_policy": "dots",
                               "opt_sharding": "zero1",
                               "grad_accum_dtype": "bfloat16"})),
        ],
    },
    # 3) most collective/memory-bound serving cell: MHA decode at 32k.
    "musicgen_decode": {
        "arch": "musicgen-large", "shape": "decode_32k",
        "variants": [
            ("baseline_seq_cache", dict()),
            ("heads_cache", dict(
                cfg_overrides={"decode_cache_sharding": "heads"})),
            ("dp32_tp8", dict(mesh=((32, 8), ("data", "model")))),
            ("dp128_tp2", dict(mesh=((128, 2), ("data", "model")))),
        ],
    },
}


def mesh_dict(mesh):
    return dict(zip(mesh.axis_names,
                    (mesh.shape[a] for a in mesh.axis_names)))


def run_variant(arch, shape_name, name, spec, outdir):
    mesh_spec = spec.get("mesh", ((16, 16), ("data", "model")))
    mesh = make_mesh(*mesh_spec)
    mb = spec.get("microbatches")
    cfg_over = spec.get("cfg_overrides", {})
    rec = run_cell(arch, shape_name, mesh, f"{mesh_spec[0]}", outdir=None,
                   microbatches=mb, cfg_overrides=cfg_over,
                   overrides=spec.get("rules"))
    cfg = get_config(arch)
    if cfg_over:
        cfg = cfg.replace(**{k: v for k, v in cfg_over.items()
                             if not k.startswith("moe_")})
    roof = cell_roofline(cfg, SHAPES[shape_name], mesh_dict(mesh),
                         microbatches=mb)
    coll = rec["collectives"]
    out = {
        "variant": name,
        "mesh": mesh_spec[0], "microbatches": rec["microbatches"],
        "cfg_overrides": cfg_over,
        "roofline": {k: roof[k] for k in
                     ("compute_s", "memory_s", "collective_s", "dominant",
                      "step_s", "mfu", "useful_ratio", "hbm_need_gib",
                      "fits")},
        "xla": {
            "coll_bytes_bodyonce": sum(v["bytes"] for v in coll.values()),
            "coll_counts": {k: v["count"] for k, v in coll.items()
                            if v["count"]},
            "mem_device_gib": rec["mem_device_bytes"] / 2**30,
            "mem_tpu_est_gib": (rec["mem_device_tpu_est_bytes"] or 0) / 2**30
            if rec.get("mem_device_tpu_est_bytes") else None,
            "compile_s": rec["compile_s"],
        },
    }
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", choices=sorted(CELLS), required=True)
    ap.add_argument("--variants", nargs="*", default=None)
    args = ap.parse_args(argv)
    cell = CELLS[args.cell]
    ART.mkdir(parents=True, exist_ok=True)
    path = ART / f"{args.cell}.json"
    results = json.loads(path.read_text()) if path.exists() else []
    done = {r["variant"] for r in results}
    for name, spec in cell["variants"]:
        if args.variants and name not in args.variants:
            continue
        if name in done:
            print(f"[skip] {name} (cached)")
            continue
        print(f"[run] {args.cell}/{name} ...", flush=True)
        out = run_variant(cell["arch"], cell["shape"], name, spec, ART)
        results.append(out)
        path.write_text(json.dumps(results, indent=1))
        r, x = out["roofline"], out["xla"]
        print(f"  step={r['step_s']*1e3:.1f}ms dom={r['dominant'][:-2]} "
              f"mfu={r['mfu']*100:.1f}% coll(xla,1-body)="
              f"{x['coll_bytes_bodyonce']/2**20:.0f}MiB "
              f"mem={x['mem_device_gib']:.1f}GiB "
              f"compile={x['compile_s']}s", flush=True)
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
