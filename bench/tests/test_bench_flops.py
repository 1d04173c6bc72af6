"""The dense family's FLOP count, the table of peaks, and the shape of
BENCHMARK.json."""
import json
import re
from pathlib import Path

import pytest

from bench import feed, flops, run

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DENSE = run.family("dense")


def load(kind, name):
    return feed.load(BENCH / kind / f"{name}.json")


@pytest.mark.parametrize("config,mix,want", [
    ("yi-6b.d1", "pack.s2048.b8", 4.44e13),
    ("deepseek-7b.d8", "pack.s2048.b8", 2.14e14),
])
def test_step_flops(config, mix, want):
    got = DENSE.step_flops(load("configs", config), load("traffic", mix))
    assert got == pytest.approx(want, rel=0.01)


@pytest.mark.parametrize("mix,want", [
    ("pack.s2048.b8", 44427141709824), ("pack.s4096.b4", 46076409151488)])
def test_yi_step_flops_exactly(mix, want):
    """``flops_per_step`` of the yi-6b cells, as an integer: the count
    that ``mfu`` divides."""
    got = DENSE.step_flops(load("configs", "yi-6b.d1"), load("traffic", mix))
    assert got == want


def test_matmul_params_leave_out_the_embedding():
    yi = load("configs", "yi-6b.d1")
    # 1 layer of 173.0M (q, k, v, o, gated FFN) + the 262.1M LM head
    assert DENSE.matmul_params(yi) == 4096 * (4096 * 2 + 512 * 2) \
        + 3 * 4096 * 11008 + 4096 * 64000


def test_same_tokens_longer_rows_add_only_attention():
    yi = load("configs", "yi-6b.d1")
    a = DENSE.step_flops(yi, load("traffic", "pack.s2048.b8"))
    b = DENSE.step_flops(yi, load("traffic", "pack.s4096.b4"))
    assert b - a == pytest.approx(12 * 32 * 128 * (4096 - 2048) * 16384)


def test_peaks_are_keyed_by_device_kind_with_a_source():
    table = json.loads((BENCH / "devices.json").read_text())
    v5e = flops.peaks("TPU v5 lite", table)
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["source"]
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary", table)


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_json_names_its_files():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert sorted(cfg.get("reduced", {})) == sorted(c["reduced"])
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert (BENCH / "limits" / f"{w['name']}.json").is_file()
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= \
        max(1, len(b["workloads"]) // 2)
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in {e["name"] for e in b["end_to_end"]}
