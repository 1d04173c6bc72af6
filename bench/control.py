"""Upper readings of the check: the float32 reference put in the program's
place, computed in float8 (the control) or with a planted fault, and
compared with the float32 reference as the benchmark compares the program.

    python3 bench/control.py --workload yi6b.train.s2048 --seeds 11,12,13 \
        --modes fp8,half

Modes: ``fp8``, every matmul operand rounded to float8_e4m3 (the precision
below the configuration's bfloat16); ``half``, each step on half of its
microbatches, the mean taken over those; ``exchange``, the sums across
the ``model`` axis left out of the layers (cells on more than one chip).
A state left unchanged reads ``change_gap`` 1 by construction and needs no
run.  One JSON line per seed and mode; the benchmark's own runs never run
this.  Exits non-zero without a TPU, as ``run.py`` does.
"""
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
if sys.path and Path(sys.path[0]).resolve() == HERE:
    sys.path[0] = str(HERE.parent)

from bench import check, feed, reference, run  # noqa: E402

MODES = {"fp8": dict(precision="fp8"), "half": dict(half=True),
         "exchange": dict(exchange=False)}


def readings(cell, seed: int, modes, devices) -> list:
    stream = feed.tokens(cell.config["vocab_size"], cell.mix, seed,
                         run.SETUP_STEPS)

    def ref_run(**kw):
        t0 = time.perf_counter()
        out = reference.run(cell.family, cell.config, cell.mix, seed,
                            stream, len(devices), **kw)
        return out, time.perf_counter() - t0

    ref, ref_s = ref_run()
    out = []
    for mode in modes:
        got, s = ref_run(**MODES[mode])
        values = {k: v for k, (v, _) in check.readings(got, ref).items()}
        values["tokens_mismatch"] = 0.0
        values["nonfinite_losses"] = 0.0
        correct, _ = check.judge(values, cell.limits)
        out.append({"seed": seed, "mode": mode, "correct": correct,
                    "readings": values, "seconds": [ref_s, s]})
    return out


def main(argv=None, *, root: Path = run.ROOT, look_for_chip=True) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--modes", default="fp8,half")
    args = ap.parse_args(argv)
    cell = run.resolve(args.workload, root)
    modes = args.modes.split(",")
    import jax
    if look_for_chip:
        try:
            devices = run.find_chips(cell.chips)
        except run.NoChip as e:
            run.log(f"control: {e}")
            return 3
        from repro.launch.__main__ import use_compile_cache
        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    else:
        devices = jax.devices()[:cell.chips]
    for seed in (int(s) for s in args.seeds.split(",")):
        for line in readings(cell, seed, modes, devices):
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
