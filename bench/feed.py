"""Traffic: a mix file's parameters turned into the trainer's input pipeline,
and the benchmark's own copy of the token stream to check it against.

The copy is ``runtime/pipeline.py``'s corpus and packing as the benchmark
holds them: per-document generators keyed by ``(seed, document index)``,
document lengths ``max(8, Exponential(mean_doc_len))``, Zipf(``zipf_a``)
ids folded into ``[2, vocab)``, documents packed back to back with an EOS
(id 1) after each, cut into rows of ``seq_len`` tokens."""
from __future__ import annotations

import json

import numpy as np


def load(path) -> dict:
    with open(path) as f:
        return json.load(f)


def tokens(vocab: int, mix: dict, seed: int, steps: int) -> np.ndarray:
    """The first ``steps`` batches of the stream: [steps, m, mb, seq]."""
    m, t = mix["microbatches"], mix["seq_len"]
    need = steps * mix["global_batch"] * t
    parts, have, doc = [], 0, 0
    while have < need:
        rng = np.random.default_rng((seed, doc))
        doc += 1
        n = max(8, int(rng.exponential(mix["mean_doc_len"])))
        ids = rng.zipf(mix["zipf_a"], size=n) % (vocab - 2) + 2
        parts += [ids.astype(np.int32), np.ones(1, np.int32)]
        have += n + 1
    flat = np.concatenate(parts)[:need]
    return flat.reshape(steps, m, mix["global_batch"] // m, t)


def pipeline(model_cfg, mix: dict, seed: int, sharding):
    """The trainer's ``DataPipeline`` for this mix, started."""
    from repro.configs import ShapeConfig
    from repro.runtime.pipeline import DataPipeline, PipelineConfig

    shape = ShapeConfig("bench", "train", mix["seq_len"], mix["global_batch"])
    pcfg = PipelineConfig(seed=seed, prefetch=mix["prefetch"],
                          mean_doc_len=mix["mean_doc_len"],
                          zipf_a=mix["zipf_a"])
    return DataPipeline(model_cfg, shape, pcfg, sharding=sharding).start()
