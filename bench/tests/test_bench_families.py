"""A configuration names its architecture (``bench/families/<family>.py``),
and that module alone gives the trainer's config, the reference's leaves
and loss, and the FLOPs: a new architecture arrives as files.  The dense
family computes what the reference computed before it moved there."""
import json
import re
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import feed, reference, run
from bench.tests import tiny
from bench.tests.test_bench_harness import digest

BENCH = Path(__file__).resolve().parents[1]
PINNED = json.loads((BENCH / "tests" / "data" / "dense_reference.json")
                    .read_text())

# dense, with a marker leaf, a loss one higher, a sentinel FLOP count and
# a trainer config of its own name
MARKED = (BENCH / "families" / "dense.py").read_text() + '''

MARK = "marked"
_dense_trainer_config, _dense_specs, _dense_loss = trainer_config, specs, loss


def trainer_config(c, mix):
    return _dense_trainer_config(c, mix).replace(name="marked")


def specs(cfg):
    return {**_dense_specs(cfg),
            "marker": ((1,), cfg["torch_dtype"], 0.0, P())}


def loss(cfg, p, tokens, ein, reduce):
    return _dense_loss(cfg, p, tokens, ein, reduce) + 1.0


def step_flops(cfg, mix):
    return 12345.0
'''


def two_steps(fam, precision="f32", seed=5):
    stream = feed.tokens(tiny.CONFIG["vocab_size"], tiny.MIX, seed, 2)
    return reference.run(fam, tiny.CONFIG, tiny.MIX, seed, stream, 1,
                         precision=precision)


@pytest.mark.parametrize("precision", ["f32", "fp8"])
def test_dense_reference_moved_nothing(precision):
    """Losses and every leaf's norms, bit for bit, as pinned."""
    got = two_steps(run.family("dense"), precision)
    assert got == PINNED[precision]


def write_marked(root: Path) -> Path:
    tiny.write(root)
    (root / "bench" / "families" / "marked.py").write_text(MARKED)
    (root / "bench" / "configs" / "tinym.json").write_text(
        json.dumps({**tiny.CONFIG, "family": "marked"}))
    (root / "bench" / "limits" / "tiny.marked.json").write_text(
        json.dumps(tiny.LIMITS))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tinym", "source": "tiny",
                         "file": "bench/configs/tinym.json", "reduced": [],
                         "why": "tiny"})
    b["workloads"].append({"name": "tiny.marked", "config": "tinym",
                           "traffic": "tiny", "chips": 1, "why": "tiny"})
    (root / "BENCHMARK.json").write_text(json.dumps(b, indent=1))
    return root


def test_new_family_from_files_only(tmp_path):
    before = digest(BENCH)
    cell = run.resolve("tiny.marked", write_marked(tmp_path))
    assert cell.family.MARK == "marked"
    assert run.resolve("tiny.cell", tmp_path).family.__name__ == \
        "bench_family_dense"
    # the trainer's config
    assert run.program_config(cell).name == "marked"
    # mfu's FLOPs
    assert run.flops_per_step(cell) == 12345.0
    mfu = run.metric_reader(cell, "mfu")
    assert mfu(SimpleNamespace(steps=2, flops_per_step=run.flops_per_step(
        cell), window_s=1.0, chips=1, peak_flops=24690.0)) == 100.0
    # the reference's leaves and loss
    got = two_steps(cell.family)
    assert set(got["grad"]) == set(PINNED["f32"]["grad"]) | {"marker"}
    for mine, dense in zip(got["loss"], PINNED["f32"]["loss"]):
        assert mine == pytest.approx(dense + 1.0, rel=1e-6)
    assert digest(BENCH) == before


@pytest.mark.parametrize("family,error", [
    (None, ValueError), ("no_such_family", FileNotFoundError)])
def test_config_without_its_family_is_an_error(tmp_path, family, error):
    root = tiny.write(tmp_path)
    path = root / "bench" / "configs" / "tiny.json"
    cfg = {k: v for k, v in tiny.CONFIG.items() if k != "family"}
    if family is not None:
        cfg["family"] = family
    path.write_text(json.dumps(cfg))
    with pytest.raises(error):
        run.resolve("tiny.cell", root)


@pytest.mark.parametrize("module", ["run.py", "control.py", "reference.py",
                                    "flops.py", "check.py"])
def test_no_architecture_outside_families(module):
    """Leaf names and the trainer's architecture options live in the
    families alone."""
    src = (BENCH / module).read_text()
    names = re.compile(r"attn/|ffn/|\bln[12]\b|final_norm|tok_emb|stages/|"
                       r"\bdeparts\b|\bmoe\b|\bmla\b|\bssm\b|meta_tokens")
    assert not names.findall(src)


@pytest.mark.parametrize("config", sorted(
    p.name for p in (BENCH / "configs").glob("*.json")))
def test_every_configuration_names_a_family(config):
    fam = run.family(feed.load(BENCH / "configs" / config)["family"])
    for fn in ("trainer_config", "specs", "is_matrix", "loss", "step_flops"):
        assert callable(getattr(fam, fn)), fn
