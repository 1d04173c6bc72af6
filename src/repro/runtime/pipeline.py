"""Deterministic sharded data pipeline for LM training.

Host-side token stream -> packed fixed-length sequences -> device batches
laid out as [microbatches, batch, seq] and sharded over the mesh batch axes.
A background prefetch thread keeps ``prefetch`` batches in flight so host
data work overlaps device compute (the standard input-pipeline overlap).

The synthetic corpus is a seeded Zipfian token source (real pipelines swap
in a tokenized corpus reader; the interface is identical), with documents of
random length separated by EOS and *packed* -- no padding waste.
"""
from __future__ import annotations

import queue
import threading
from dataclasses import dataclass

import jax
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.runtime import obs


@dataclass(frozen=True)
class PipelineConfig:
    seed: int = 0
    prefetch: int = 2
    mean_doc_len: int = 512
    zipf_a: float = 1.2


class SyntheticCorpus:
    """Seeded, restartable document stream (stand-in for a corpus reader)."""

    def __init__(self, vocab: int, cfg: PipelineConfig, start_doc: int = 0):
        self.vocab = vocab
        self.cfg = cfg
        self.doc_index = start_doc

    def next_doc(self) -> np.ndarray:
        # per-document RNG keyed by (seed, doc_index): deterministic resume
        rng = np.random.default_rng((self.cfg.seed, self.doc_index))
        self.doc_index += 1
        n = max(8, int(rng.exponential(self.cfg.mean_doc_len)))
        toks = rng.zipf(self.cfg.zipf_a, size=n) % (self.vocab - 2)
        return toks.astype(np.int32) + 2                 # 0=pad, 1=eos


class PackedBatcher:
    """Pack documents into fixed-length rows with EOS separators."""

    def __init__(self, corpus: SyntheticCorpus, seq_len: int):
        self.corpus = corpus
        self.seq_len = seq_len
        self._buf = np.zeros(0, np.int32)

    def next_rows(self, n_rows: int) -> np.ndarray:
        need = n_rows * self.seq_len
        parts = [self._buf]
        have = len(self._buf)
        while have < need:
            doc = self.corpus.next_doc()
            parts.append(doc)
            parts.append(np.array([1], np.int32))        # eos
            have += len(doc) + 1
        flat = np.concatenate(parts)
        self._buf = flat[need:]
        return flat[:need].reshape(n_rows, self.seq_len)

    def state(self) -> dict:
        return {"doc_index": self.corpus.doc_index,
                "buf": self._buf.tolist()}

    def restore(self, state: dict) -> None:
        self.corpus.doc_index = state["doc_index"]
        self._buf = np.asarray(state["buf"], np.int32)


class DataPipeline:
    """Batches shaped [m, b, ...] with a prefetch thread; checkpointable."""

    def __init__(self, model_cfg: ModelConfig, shape: ShapeConfig,
                 pcfg: PipelineConfig = PipelineConfig(), sharding=None):
        self.cfg = model_cfg
        self.shape = shape
        self.pcfg = pcfg
        self.sharding = sharding
        self.batcher = PackedBatcher(
            SyntheticCorpus(model_cfg.vocab, pcfg), shape.seq_len)
        self._q: queue.Queue = queue.Queue(maxsize=max(1, pcfg.prefetch))
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._step = 0
        self._consumed = self._cursor()

    # -------------------------------------------------------------- build
    def _build(self) -> dict:
        m = self.cfg.train_microbatches
        b = self.shape.global_batch // m
        t = self.shape.seq_len
        t_text = t - (self.cfg.image_tokens if self.cfg.frontend == "vision" else 0)
        if self.cfg.n_codebooks > 1:
            rows = self.batcher.next_rows(m * b * self.cfg.n_codebooks)
            toks = rows.reshape(m, b, self.cfg.n_codebooks, t_text)
        else:
            rows = self.batcher.next_rows(m * b)[:, :t_text]
            toks = rows.reshape(m, b, t_text)
        batch = {"tokens": toks}
        if self.cfg.frontend == "vision":
            rng = np.random.default_rng((self.pcfg.seed, 10_000_019, self._step))
            batch["image_embeds"] = rng.normal(
                0, 0.02, (m, b, self.cfg.image_tokens, self.cfg.d_model)
            ).astype(np.float32)
        self._step += 1
        return batch

    def _put_device(self, batch):
        if self.sharding is not None:
            return {k: jax.device_put(v, self.sharding[k])
                    for k, v in batch.items()}
        return jax.tree.map(jax.numpy.asarray, batch)

    # ------------------------------------------------------------ iterate
    def _cursor(self) -> dict:
        return {"batcher": self.batcher.state(), "step": self._step}

    def _worker(self):
        # every batch built is handed out, in order: a full queue makes the
        # producer wait, never skip
        while not self._stop.is_set():
            item = (self._build(), self._cursor())
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.2)
                    break
                except queue.Full:
                    continue

    def start(self):
        if self._thread is None:
            self._stop.clear()
            self._thread = threading.Thread(target=self._worker, daemon=True)
            self._thread.start()
        return self

    def __next__(self):
        with obs.span("pipeline.wait"):
            if self._thread is None:
                batch = self._build()
                self._consumed = self._cursor()
            else:
                batch, self._consumed = self._q.get()
        with obs.span("pipeline.put"):
            return self._put_device(batch)

    def __iter__(self):
        return self

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        self._q = queue.Queue(maxsize=max(1, self.pcfg.prefetch))

    # --------------------------------------------------------- checkpoint
    def state(self) -> dict:
        """The stream position just past the last batch handed out.  Batches
        still in the prefetch queue are not counted: after a restore they
        are produced again, so a resumed run sees every batch once."""
        return self._consumed

    def restore(self, state: dict) -> None:
        running = self._thread is not None
        self.stop()                        # drop what was prefetched
        self.batcher.restore(state["batcher"])
        self._step = state["step"]
        self._consumed = self._cursor()
        if running:
            self.start()
