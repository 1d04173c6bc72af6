"""A benchmark tree of tiny cells, written as files only, for the CPU tests:
the same harness, at widths a test run can hold."""
import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

CONFIG = {"source": "tiny test configuration", "arch": "yi-6b",
          "family": "dense",
          "hidden_size": 64, "intermediate_size": 128,
          "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
          "num_hidden_layers": 2, "vocab_size": 256,
          "max_position_embeddings": 64, "rope_theta": 10000.0,
          "rms_norm_eps": 1e-05, "hidden_act": "silu",
          "tie_word_embeddings": False, "torch_dtype": "bfloat16"}

MIX = {"seq_len": 64, "global_batch": 4, "microbatches": 2,
       "mean_doc_len": 16, "zipf_a": 1.2, "prefetch": 2, "peak_lr": 1e-3,
       "warmup": 0, "total_steps": 1000000,
       "adamw": {"b1": 0.9, "b2": 0.95, "eps": 1e-08, "weight_decay": 0.1,
                 "clip": 1.0}}

# Set from readings at this size on the CPU, seeds 1-6: the program's
# largest loss gaps 4.2e-5 (step 1) and 1.8e-4 (step 2), grad_gap 7.3e-4,
# change_gap 8.0e-4; the float8 control's smallest loss_gap.step1 2.2e-4 and
# grad_gap 5.7e-3; half the batch's smallest change_gap 0.149.
LIMITS = {"tokens_mismatch": 0, "nonfinite_losses": 0,
          "loss_gap.step1": 1e-4, "loss_gap.step2": 1e-3,
          "grad_gap": 2.5e-3, "change_gap": 5e-3}


def write(root: Path, metric_src: str = None) -> Path:
    """``root`` holding BENCHMARK.json and ``bench/``: the real metric
    readers, families and devices table, tiny configurations, a tiny mix
    and limits.
    ``metric_src`` adds a per-layer metric ``tiny_metric`` with that
    reader's source."""
    b = root / "bench"
    for d in ("configs", "traffic", "limits", "metrics", "families"):
        (b / d).mkdir(parents=True, exist_ok=True)
    for d in ("metrics", "families"):
        for f in (BENCH / d).glob("*.py"):
            shutil.copy(f, b / d / f.name)
    shutil.copy(BENCH / "devices.json", b / "devices.json")
    (b / "configs" / "tiny.json").write_text(json.dumps(CONFIG))
    (b / "configs" / "tiny4.json").write_text(
        json.dumps({**CONFIG, "num_key_value_heads": 4}))
    (b / "traffic" / "tiny.json").write_text(json.dumps(MIX))
    for cell in ("tiny.cell", "tiny.tp4"):
        (b / "limits" / f"{cell}.json").write_text(json.dumps(LIMITS))
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["configs"] = [
        {"name": n, "source": "tiny", "file": f"bench/configs/{n}.json",
         "reduced": [], "why": "tiny"} for n in ("tiny", "tiny4")]
    bench["workloads"] = [
        {"name": "tiny.cell", "config": "tiny", "traffic": "tiny",
         "chips": 1, "why": "tiny"},
        {"name": "tiny.tp4", "config": "tiny4", "traffic": "tiny",
         "chips": 4, "why": "tiny"}]
    for m in bench["per_layer"]:
        m.pop("workloads", None)
    if metric_src is not None:
        (b / "metrics" / "tiny_metric.py").write_text(metric_src)
        bench["per_layer"].append(
            {"name": "tiny_metric", "unit": "ms", "better": "lower",
             "source": "host_clock", "layer": "input pipeline",
             "moves": "train_tokens_per_s", "workloads": ["tiny.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root
