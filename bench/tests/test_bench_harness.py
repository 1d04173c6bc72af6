"""The harness is driven by data: a new configuration, traffic mix, limits
and per-layer metric are files found by name.  And it refuses to measure
without a chip."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import run
from bench.tests import tiny

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def digest(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_new_cell_from_files_only(tmp_path):
    before = digest(BENCH)
    root = tiny.write(tmp_path, metric_src=(
        "def read(run):\n"
        "    return 2.0 * len(run.input_wait_s)\n"))
    cell = run.resolve("tiny.cell", root)
    assert cell.config["hidden_size"] == 64 and cell.chips == 1
    assert cell.mix["seq_len"] == 64
    assert cell.limits["grad_gap"] == tiny.LIMITS["grad_gap"]
    names = [m["name"] for m in cell.per_layer]
    assert "tiny_metric" in names and "mfu" in names
    reader = run.metric_reader(cell, "tiny_metric")
    assert reader(type("Run", (), {"input_wait_s": [1, 2, 3]})) == 6.0
    # a metric listed for other cells only is not this cell's
    assert "tiny_metric" not in [
        m["name"] for m in run.resolve("tiny.tp4", root).per_layer]
    assert digest(BENCH) == before


def test_unknown_cell_is_an_error(tmp_path):
    with pytest.raises(KeyError):
        run.resolve("no.such.cell", tiny.write(tmp_path))


def _bench(cwd, *args):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTHONPATH")}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "yi6b.train.s2048",
         "--seed", "2147483701", "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_exits_nonzero_without_a_tpu():
    r = _bench(ROOT)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert r.stdout.strip() == ""


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _bench(tmp_path)
    assert r.returncode != 0
    assert not any(line.startswith("{") for line in r.stdout.splitlines())


def test_every_cell_resolves():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in b["workloads"]:
        cell = run.resolve(w["name"])
        assert set(cell.limits) <= set(tiny.LIMITS)
        assert {"tokens_mismatch", "nonfinite_losses"} <= set(cell.limits)
        assert [m["name"] for m in cell.end_to_end] == \
            ["train_tokens_per_s", "setup_s"]
        assert cell.family.__name__ == f"bench_family_{cell.config['family']}"
