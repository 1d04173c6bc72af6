"""The named parts of the step program and the readers of what the program
records about itself: the scope table of the tiny step as the trainer
compiles it, and each reader on a hand-made run."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import inside, run, scopes, trace as T
from bench.tests import tiny
from repro.runtime import obs

BENCH = Path(__file__).resolve().parents[1]
STACK = "jit(train_step)/while/body/closed_call"
BWD = f"{STACK}/transpose(jvp())/while/body/closed_call/checkpoint"


@pytest.fixture(scope="module")
def tiny_table(tmp_path_factory):
    from repro.configs import ShapeConfig
    from repro.launch import train
    from repro.runtime.elastic import make_plan_mesh, plan_mesh
    from repro.runtime.steps import TrainHParams

    cell = run.resolve("tiny.cell", tiny.write(tmp_path_factory.mktemp("t")))
    mix = cell.mix
    mesh = make_plan_mesh(plan_mesh(1, mix["global_batch"], prefer_model=1,
                                    microbatches=mix["microbatches"]))
    shape = ShapeConfig("bench", "train", mix["seq_len"],
                        mix["global_batch"])
    train.build(run.program_config(cell), shape, mesh, TrainHParams())
    return scopes.program_table("jit_train_step")


@pytest.mark.parametrize("part,phases", [
    ("attention", {"forward", "backward", "recompute"}),
    ("ffn", {"forward", "backward", "recompute"}),
    ("embed", {"forward", "backward"}),
    ("head_loss", {"forward", "backward"}),
    ("grad_accum", {"forward"}),
    ("optimizer", {"forward"}),
])
def test_every_scope_of_the_tiny_step_is_in_its_table(tiny_table, part,
                                                       phases):
    assert {ph for p, ph in tiny_table.values() if p == part} == phases


@pytest.mark.parametrize("op_name,want", [
    (f"{STACK}/jvp()/while/body/closed_call/checkpoint/attention/"
     "btd,dhk->bthk/dot_general", ("attention", "forward")),
    (f"{STACK}/transpose(jvp(head_loss))/dot_general",
     ("head_loss", "backward")),
    (f"{BWD}/rematted_computation/ffn/mul", ("ffn", "recompute")),
    (f"{BWD}/transpose(jvp(attention))/dot_general",
     ("attention", "backward")),
    (f"{STACK}/jvp()/rsqrt", ("unscoped", "forward")),
    ("jit(train_step)/optimizer/transpose", ("optimizer", "forward")),
    ("jit(train_step)/jit(transpose)/add", ("unscoped", "forward")),
])
def test_name_stacks_to_part_and_phase(op_name, want):
    assert scopes.classify(op_name) == want


# (instruction, name stack, device ms per step) of a hand-made module
OPS = [
    ("attn_f.1", f"{STACK}/jvp()/checkpoint/attention/dot_general", 1.0),
    ("attn_r.2", f"{BWD}/rematted_computation/attention/exp", 2.0),
    ("attn_b.3", f"{BWD}/transpose(jvp(attention))/dot_general", 4.0),
    ("ffn_f.4", f"{STACK}/jvp()/checkpoint/ffn/mul", 8.0),
    ("head_b.5", f"{STACK}/transpose(jvp(head_loss))/dot_general", 16.0),
    ("embed_b.6", f"{STACK}/transpose(jvp(embed))/scatter-add", 32.0),
    ("gacc.7", "jit(train_step)/while/body/grad_accum/add", 64.0),
    ("opt.8", "jit(train_step)/optimizer/mul", 128.0),
    ("norm.9", f"{STACK}/jvp()/rsqrt", 256.0),
]
MODULE = "jit_hand_made_step"
STEPS = 3                                  # window steps
MS = 1e6                                   # ns per ms

# the fake clock's spans: two set-up steps, then the window's three
WAIT_MS = [50.0, 40.0, 3.0, 5.0, 7.0]
PUT_MS = [9.0, 9.0, 1.0, 2.0, 6.0]


class Module:
    def __init__(self, text):
        self.text = text

    def as_text(self):
        return self.text


def hand_made_text() -> str:
    lines = [f"HloModule {MODULE}, is_scheduled=true", "",
             "ENTRY %main (p: f32[8]) -> f32[8] {"]
    lines += [f'  %{n} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop, '
              f'metadata={{op_name="{s}" stack_frame_id=1}}'
              for n, s, _ in OPS]
    lines += ["  ROOT %w = f32[8]{0} while(f32[8]{0} %p), condition=%c, "
              'body=%b, metadata={op_name="jit(train_step)/while"}', "}"]
    return "\n".join(lines)


class Clock:
    now = 0

    @classmethod
    def perf_counter_ns(cls):
        return cls.now


@pytest.fixture
def hand_made_run(monkeypatch):
    obs.reset()
    obs.note_program(Module(hand_made_text()))
    # two chips: the second takes 3x as long; the mean over chips / STEPS
    # steps is the table's ms
    devices = {}
    for chip, k in ((0, 1.0), (1, 3.0)):
        ops = [T.Op(n, "fusion", 0.0, k * ms * STEPS / 2 * 1e-3)
               for n, _, ms in OPS]
        ops += [T.Op("mystery.10", "fusion", 1.0, k * 512 * STEPS / 2e3),
                T.Op("w", "while", 0.0, 9.0),
                T.Op("attn_f.1", "fusion", 20.0, 1.0)]      # after the window
        devices[chip] = T.Device(ops)
    monkeypatch.setattr(obs, "time", Clock)
    window_start = None
    for i, (w, p) in enumerate(zip(WAIT_MS, PUT_MS)):
        if i == 2:
            window_start = Clock.now
        with obs.span("pipeline.wait"):
            Clock.now += int(w * MS)
        with obs.span("pipeline.put"):
            Clock.now += int(p * MS)
        if i in (0, 3):                    # one in set-up, one in the window
            obs._on_duration(obs.COMPILE_EVENT, 0.5)
    Clock.now = window_start + int(1000 * MS)     # just past the window
    obs._on_duration(obs.COMPILE_EVENT, 0.5)
    obs._on_duration("/jax/some/other/event", 0.5)
    yield SimpleNamespace(
        steps=STEPS, window_s=0.9, trace=T.Trace(devices, []),
        trace_window=(0.0, 10.0), step_module=MODULE)
    obs.reset()


# what each reader reads from the hand-made run
WANT = {
    "attention_device_ms": 1.0 + 2.0 + 4.0,
    "ffn_device_ms": 8.0,
    "head_loss_device_ms": 16.0,
    "embed_device_ms": 32.0,
    "grad_accum_device_ms": 64.0,
    "optimizer_device_ms": 128.0,
    "recompute_device_ms": 2.0,
    "unscoped_device_ms": 256.0 + 512.0,         # the norm and the unknown op
    "input_queue_ms": (3.0 + 5.0 + 7.0) / 3,      # the window's, not set-up's
    "input_put_ms": (1.0 + 2.0 + 6.0) / 3,
    "window_compiles": 1,
}


@pytest.mark.parametrize("metric,want", WANT.items())
def test_reader_on_a_hand_made_run(hand_made_run, metric, want):
    read = run.metric_reader(SimpleNamespace(root=BENCH.parent), metric)
    assert read(hand_made_run) == pytest.approx(want)


@pytest.mark.parametrize("metric", WANT)
def test_reader_reads_nothing_from_a_program_without_records(
        hand_made_run, monkeypatch, metric):
    """A program that predates ``repro.runtime.obs`` is read as silent."""
    monkeypatch.setattr(inside, "obs", lambda: None)
    read = run.metric_reader(SimpleNamespace(root=BENCH.parent), metric)
    assert read(hand_made_run) is None
