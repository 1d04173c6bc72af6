"""A whole benchmark run at a tiny size on the CPU (the look for a chip
skipped): correct as the program stands, and not correct with the timed
path broken underneath, or with the float8 control in its place."""
import json

import pytest

from bench import control, run
from bench.tests import tiny
from repro.launch import train


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.write(tmp_path_factory.mktemp("tiny"))


def bench_run(root, capsys, seed=5):
    rc = run.main(["--workload", "tiny.cell", "--seed", str(seed),
                   "--seconds", "0.5", "--trace", "0"], root=root,
                  look_for_chip=False)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(out)[-1] == "checks"
    return out


def test_sound_run_is_correct(root, capsys):
    out = bench_run(root, capsys)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert out["device"]["count"] == 1


def unchanged(make):
    """A step that returns its state unchanged (the loss still computed)."""
    def faulty(cfg, hp, **kw):
        real = make(cfg, hp, **kw)
        return lambda p, s, b, i: (p, s, real(p, s, b, i)[2])
    return faulty


def half_batch(make):
    """Half of the batch left out, the mean taken over the rest."""
    def faulty(cfg, hp, **kw):
        m = cfg.train_microbatches
        real = make(cfg.replace(train_microbatches=m // 2), hp, **kw)
        return lambda p, s, b, i: real(
            p, s, {k: v[: m // 2] for k, v in b.items()}, i)
    return faulty


@pytest.mark.parametrize("fault", [unchanged, half_batch])
def test_broken_step_is_not_correct(root, capsys, monkeypatch, fault):
    monkeypatch.setattr(train, "make_train_step",
                        fault(train.make_train_step))
    out = bench_run(root, capsys)
    assert out["correct"] is False, out["checks"]


def test_altered_batch_is_not_correct(root, capsys, monkeypatch):
    """A token altered where the pipeline produces it."""
    from repro.runtime import pipeline
    build = pipeline.DataPipeline._build

    def altered(self):
        batch = build(self)
        batch["tokens"][0, 0, 3] += 1
        return batch
    monkeypatch.setattr(pipeline.DataPipeline, "_build", altered)
    out = bench_run(root, capsys)
    assert out["correct"] is False
    assert out["checks"]["tokens_mismatch"]["value"] > 0


@pytest.mark.parametrize("mode", ["fp8", "half"])
def test_control_is_not_correct(root, capsys, mode):
    assert control.main(["--workload", "tiny.cell", "--seeds", "5",
                         "--modes", mode], root=root,
                        look_for_chip=False) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["mode"] == mode and line["correct"] is False, line
