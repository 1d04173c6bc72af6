"""Train-step benchmark of one cell of BENCHMARK.json on TPU chips.

    python3 bench/run.py --workload yi6b.train.s2048 --seed 7 --seconds 10 --trace 0

A cell is a configuration (``bench/configs/``) under a traffic mix
(``bench/traffic/``); both, the cell's correctness limits
(``bench/limits/<cell>.json``) and each per-layer metric's reader
(``bench/metrics/<metric>.py``) are found by the names in BENCHMARK.json.
The configuration names its architecture's module
(``bench/families/<family>.py``): the trainer's config for it, its leaves,
its loss for the reference and its FLOPs.

Set-up builds the trainer's compiled, donated step (``train.build``) on
the planned mesh, makes the weights from ``--seed`` on the device, starts
the trainer's input pipeline, and runs the first two steps, whose results
are kept for the check.  The window then runs ``train.main``'s per-step
sequence (next batch, step, blocking read of the loss) for ``--seconds``.
After it, the program's state is freed and the float32 reference
(``bench/reference.py`` with the family's loss) recomputes the first two
steps; ``bench/check.py`` compares them.  ``--trace 1`` records the window
with the profiler and reports the per-layer metrics instead of the
end-to-end ones.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced), with
the numbers compared under ``checks``.  With no TPU, or fewer chips than
the cell asks for, it exits non-zero and prints no result.
"""
import time

T0 = time.perf_counter()

import argparse                                               # noqa: E402
import contextlib                                             # noqa: E402
import gc                                                     # noqa: E402
import importlib.util                                         # noqa: E402
import json                                                   # noqa: E402
import math                                                   # noqa: E402
import shutil                                                 # noqa: E402
import sys                                                    # noqa: E402
import tempfile                                               # noqa: E402
from dataclasses import dataclass, field                      # noqa: E402
from pathlib import Path                                      # noqa: E402
from types import ModuleType, SimpleNamespace                 # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
if sys.path and Path(sys.path[0]).resolve() == HERE:
    sys.path[0] = str(ROOT)                    # import this directory as `bench`
elif str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
sys.path.insert(1, str(ROOT / "src"))

from bench import check, feed, flops, weights                  # noqa: E402

SETUP_STEPS = 2                                # steps before the window


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------------------ cells

@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    family: ModuleType
    mix: dict
    limits: dict
    end_to_end: list
    per_layer: list = field(default_factory=list)
    root: Path = ROOT


def resolve(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``, with its files."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]

    config = feed.load(root / conf["file"])
    if "family" not in config:
        raise ValueError(f"{conf['file']} names no family: give it "
                         f"\"family\", a module of bench/families/")

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return Cell(
        name=name, chips=w["chips"], config=config,
        family=family(config["family"], root),
        mix=feed.load(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        limits=feed.load(root / "bench" / "limits" / f"{name}.json"),
        end_to_end=mine(bench["end_to_end"]),
        per_layer=mine(bench["per_layer"]), root=root)


def _load(path: Path, module: str) -> ModuleType:
    """The module in the file ``path``, loaded by path under the name
    ``module``."""
    spec = importlib.util.spec_from_file_location(module, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(name: str, root: Path = ROOT) -> ModuleType:
    """The architecture module ``root/bench/families/<name>.py``."""
    return _load(root / "bench" / "families" / f"{name}.py",
                 f"bench_family_{name}")


def metric_reader(cell: Cell, name: str):
    return _load(cell.root / "bench" / "metrics" / f"{name}.py",
                 f"bench_metric_{name}").read


# ----------------------------------------------------------------- device

def find_chips(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r} "
                     f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------- program

def program_config(cell: Cell):
    """The trainer's config for the cell, as the cell's family maps its
    configuration file onto the trainer's options."""
    return cell.family.trainer_config(cell.config, cell.mix)


def flops_per_step(cell: Cell) -> float:
    """Model FLOPs of one step of the cell, by its family's count."""
    return cell.family.step_flops(cell.config, cell.mix)


def check_recipe(mix: dict) -> None:
    """The trainer's AdamW constants have to be the mix's."""
    import dataclasses

    from repro.runtime.optim import AdamWConfig
    have = dataclasses.asdict(AdamWConfig())
    if have != mix["adamw"]:
        raise ValueError(f"the trainer's AdamW {have} is not the mix's "
                         f"{mix['adamw']}")


def leaf_names(tree, is_leaf=None) -> tuple:
    import jax
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree,
                                                         is_leaf=is_leaf)

    def name(path):
        return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
    return [name(p) for p, _ in flat], [x for _, x in flat], treedef


class Trainer:
    """The program's compiled step with its state, driven the way
    ``train.main`` drives it, with the benchmark's host spans around each
    call into the program."""

    def __init__(self, cell: Cell, seed: int, devices):
        import jax

        from bench import reference
        from repro.configs import ShapeConfig
        from repro.launch import train
        from repro.runtime.elastic import adapt_config, make_plan_mesh, \
            plan_mesh
        from repro.runtime.steps import TrainHParams

        mix = cell.mix
        cfg = program_config(cell)
        check_recipe(mix)
        n = len(devices)
        plan = plan_mesh(n, mix["global_batch"], prefer_model=min(4, n),
                         microbatches=mix["microbatches"])
        self.mesh = make_plan_mesh(plan)
        cfg = adapt_config(cfg, plan, mix["global_batch"])
        if cfg.train_microbatches != mix["microbatches"]:
            raise ValueError(f"the mesh {plan.shape} changes the microbatches "
                             f"to {cfg.train_microbatches}")
        shape = ShapeConfig("bench", "train", mix["seq_len"],
                            mix["global_batch"])
        hp = TrainHParams(peak_lr=mix["peak_lr"], warmup=mix["warmup"],
                          total_steps=mix["total_steps"])
        self.step_fn, (pspecs, ospecs), (p_sh, o_sh, b_sh), compile_s = \
            train.build(cfg, shape, self.mesh, hp)
        mem = self.step_fn.memory_analysis()
        self.compiled_bytes = None
        if mem is not None:
            self.compiled_bytes = (mem.argument_size_in_bytes
                                   + mem.temp_size_in_bytes)
            log(f"[setup] step program per chip: arguments "
                f"{mem.argument_size_in_bytes} B + temporaries "
                f"{mem.temp_size_in_bytes} B = {self.compiled_bytes} B; "
                f"mesh {dict(zip(self.mesh.axis_names, self.mesh.devices.shape))}"
                f"; compiled in {compile_s:.3f} s")

        # weights: the benchmark's, by the program's leaf names
        from repro.models.layers import ParamSpec
        names, leaves, self.treedef = leaf_names(
            pspecs, lambda x: isinstance(x, ParamSpec))
        want = cell.family.specs(cell.config)
        have = {nm: (tuple(s.shape), s.dtype) for nm, s in zip(names, leaves)}
        if have != {nm: (s[0], s[1]) for nm, s in want.items()}:
            raise ValueError(f"the trainer's parameters {have} are not the "
                             f"configuration's {want}")
        self.wspecs = {nm: s[:3] for nm, s in want.items()}
        self.names = names
        self.p_sh = dict(zip(names, jax.tree.leaves(p_sh)))
        self.seed = seed
        self.params = self.unflatten(weights.make(self.wspecs, seed,
                                                  self.p_sh))
        self.opt = train.init_params(ospecs, 0, o_sh)          # zeros
        self.pipe = feed.pipeline(cfg, mix, seed, b_sh)
        self.index = 0
        b1 = mix["adamw"]["b1"]
        self._grad_norms = jax.jit(lambda mu: {
            nm: jax.numpy.sqrt(jax.numpy.sum(jax.numpy.square(x))) / (1 - b1)
            for nm, x in zip(names, jax.tree.leaves(mu))})
        self._change_norms = jax.jit(lambda p, p0: reference.diff_norms(
            dict(zip(names, jax.tree.leaves(p))), p0))
        self.trace_spans = False

    def unflatten(self, flat: dict):
        return self.treedef.unflatten([flat[n] for n in self.names])

    def span(self, what: str):
        if not self.trace_spans:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(f"bench.{what}")

    def step(self):
        """One step as ``train.main`` takes it.  Returns (loss, seconds
        waiting for the batch, the batch)."""
        import jax.numpy as jnp
        t0 = time.perf_counter()
        with self.span("input_wait"):
            batch = next(self.pipe)
        t1 = time.perf_counter()
        with self.span("dispatch"):
            self.params, self.opt, metrics = self.step_fn(
                self.params, self.opt, batch, jnp.asarray(self.index,
                                                          jnp.int32))
        with self.span("loss_read"):
            loss = float(metrics["loss"])
        self.index += 1
        return loss, t1 - t0, batch

    def first_gradient_norms(self) -> dict:
        """Each leaf's norm of the gradient the optimizer got at step 1:
        its first moment after one step is (1 - b1) x that gradient."""
        return {n: float(v) for n, v in
                self._grad_norms(self.opt["mu"]).items()}

    def change_norms(self) -> dict:
        p0 = weights.make(self.wspecs, self.seed, self.p_sh)
        out = {n: float(v) for n, v in
               self._change_norms(self.params, p0).items()}
        del p0
        return out

    def close(self):
        self.pipe.stop()
        self.params = self.opt = None


# -------------------------------------------------------------------- run

def memory_peak(devices, compiled_bytes) -> int:
    """The fullest chip's peak: the allocator's ``peak_bytes_in_use`` or
    the step program's compiled arguments + temporaries, whichever is
    larger (the allocator's count leaves out the program's temporaries on
    this chip)."""
    stats = [d.memory_stats() or {} for d in devices]
    peaks = [s.get("peak_bytes_in_use", 0) for s in stats]
    log(f"[memory] peak_bytes_in_use per chip {peaks}; compiled "
        f"{compiled_bytes}")
    return int(max(peaks + [compiled_bytes or 0]))


def reduce_trace(path_dir: Path, run):
    from bench import trace as T
    files = sorted(path_dir.rglob("*.xplane.pb"))
    if not files:
        raise RuntimeError("the profiler wrote no trace")
    tr = T.load(files[-1])
    if not tr.devices:
        raise RuntimeError(f"no TPU device plane in {files[-1]}")
    run.trace = tr
    run.trace_window = T.span_window(tr, "bench.window")
    run.trace_window_s = run.trace_window[1] - run.trace_window[0]
    run.busy_s = [T.busy_s(d, run.trace_window)
                  for _, d in sorted(tr.devices.items())]
    for i, b in enumerate(run.busy_s):
        log(f"[trace] chip {i}: busy {b!r} s of {run.trace_window_s!r} s, "
            f"idle share {1 - b / run.trace_window_s!r}")
    first = tr.devices[min(tr.devices)]
    spans = [s for s in tr.spans if s[0] != "bench.window"]
    return {"device_ops": T.top_ops(tr, run.trace_window),
            "idle_gaps": T.attribute_gaps(
                T.idle_gaps(first, run.trace_window), spans)}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices, peak_flops) -> dict:
    import jax

    tr = Trainer(cell, seed, devices)
    mix = cell.mix
    tokens_per_step = mix["seq_len"] * mix["global_batch"]

    # set-up: the first steps, kept for the check
    prog = {"loss": [], "seen": []}
    for i in range(SETUP_STEPS):
        loss, _, batch = tr.step()
        prog["loss"].append(loss)
        prog["seen"].append(jax.device_get(batch["tokens"]))
        if i == 0:
            prog["grad"] = tr.first_gradient_norms()
    prog["change"] = tr.change_norms()

    trace_dir = Path(tempfile.mkdtemp(prefix="bench_trace_")) if trace \
        else None
    if trace:
        tr.trace_spans = True
        jax.profiler.start_trace(str(trace_dir))
    setup_s = time.perf_counter() - T0
    waits, losses, ends = [], [], []
    with tr.span("window"):
        t_start = time.perf_counter()
        while time.perf_counter() - t_start < seconds:
            loss, wait, _ = tr.step()
            losses.append(loss)
            waits.append(wait)
            ends.append(time.perf_counter())
        t_end = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    window_s = t_end - t_start
    steps_s = sorted(b - a for a, b in zip([t_start] + ends, ends))
    log(f"[window] {len(losses)} steps in {window_s!r} s; step seconds "
        f"min {steps_s[0]!r} median {steps_s[len(steps_s) // 2]!r} max "
        f"{steps_s[-1]!r}; losses {losses[0]!r} .. {losses[-1]!r}")

    peak = memory_peak(devices, tr.compiled_bytes)
    tr.close()
    del tr
    gc.collect()

    run = SimpleNamespace(
        steps=len(losses), window_s=window_s, chips=len(devices),
        flops_per_step=flops_per_step(cell),
        peak_flops=peak_flops, input_wait_s=waits, trace=None,
        trace_window=None, trace_window_s=None, busy_s=None,
        step_module="jit_train_step")
    breakdown = None
    if trace:
        breakdown = reduce_trace(trace_dir, run)
        shutil.rmtree(trace_dir, ignore_errors=True)

    values = compare(cell, seed, prog, devices)
    values["nonfinite_losses"] = float(
        sum(not math.isfinite(x) for x in losses))
    correct, checks = check.judge(values, cell.limits)

    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = metric_reader(cell, m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = {"train_tokens_per_s": len(losses) * tokens_per_step
               / window_s, "setup_s": setup_s}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if trace:
        device["busy_s"] = sum(run.busy_s) / len(run.busy_s)
        device["window_s"] = run.trace_window_s
    out = {"correct": bool(correct), "attempted": len(losses),
           "failed": int(values["nonfinite_losses"]), "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def compare(cell: Cell, seed: int, prog: dict, devices) -> dict:
    """The reference's two steps on the benchmark's own weights and token
    stream, and the numbers that compare the program with it."""
    import numpy as np

    from bench import reference

    stream = feed.tokens(cell.config["vocab_size"], cell.mix, seed,
                         SETUP_STEPS)
    seen = np.stack(prog["seen"])
    mismatch = float(np.sum(seen != stream)) if seen.shape == stream.shape \
        else float(stream.size)
    t0 = time.perf_counter()
    ref = reference.run(cell.family, cell.config, cell.mix, seed, stream,
                        len(devices))
    log(f"[reference] two float32 steps in {time.perf_counter() - t0:.3f} s")
    values = {"tokens_mismatch": mismatch}
    for name, (v, what) in check.readings(prog, ref).items():
        log(f"[check] {name}: {what}")
        values[name] = v
    return values


def main(argv=None, *, root: Path = ROOT, look_for_chip=True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = resolve(args.workload, root)
    import jax
    if look_for_chip:
        try:
            devices = find_chips(cell.chips)
        except NoChip as e:
            log(f"bench: {e}")
            return 3
        table = json.loads((root / "bench" / "devices.json").read_text())
        peak_flops = flops.peaks(devices[0].device_kind,
                                 table)["bf16_flops_per_s"]
    else:
        devices, peak_flops = jax.devices()[:cell.chips], None
    log(f"[device] {devices[0].platform} {devices[0].device_kind} x "
        f"{len(devices)}")

    if look_for_chip:
        from repro.launch.__main__ import use_compile_cache
        log(f"[setup] compile cache {use_compile_cache()}")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   devices, peak_flops)
    for name, c in out["checks"].items():
        log(f"check {name} = {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
