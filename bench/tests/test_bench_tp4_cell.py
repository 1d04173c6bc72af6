"""The four-chip cell ``ds7b.train.tp4`` as BENCHMARK.json and the files
under ``bench/`` give it: resolved like the one-chip cells, with the mesh's
per-layer metrics that no one-chip cell reports."""
import json
from pathlib import Path

from bench import run

ROOT = Path(__file__).resolve().parents[2]
MESH_METRICS = {"collective_ms", "collective_exposed_ms"}


def test_the_four_chip_cell():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = run.resolve("ds7b.train.tp4")
    assert cell.chips == 4 and cell.config["family"] == "dense"
    assert cell.mix["microbatches"] == 8
    assert MESH_METRICS <= {m["name"] for m in cell.per_layer}
    yi = [run.resolve(w["name"]) for w in b["workloads"]
          if w["config"] == "yi-6b.d1"]
    assert len(yi) == 2
    for other in yi:
        assert set(other.limits) <= set(cell.limits)
        assert not MESH_METRICS & {m["name"] for m in other.per_layer}
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1
