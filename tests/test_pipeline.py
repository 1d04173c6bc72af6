"""Data pipeline: determinism, checkpoint/restore resume, packing, shapes."""
import time

import numpy as np

from repro.configs import ShapeConfig, reduced_config
from repro.runtime.pipeline import (DataPipeline, PackedBatcher,
                                    PipelineConfig, SyntheticCorpus)


def mk(seed=0, mb=2, batch=4, seq=32, prefetch=2):
    cfg = reduced_config("yi-6b").replace(train_microbatches=mb)
    shape = ShapeConfig("t", "train", seq, batch)
    return DataPipeline(cfg, shape, PipelineConfig(seed=seed,
                                                   prefetch=prefetch))


def tokens(p, n):
    return [np.asarray(next(p)["tokens"]) for _ in range(n)]


def test_shapes():
    p = mk()
    b = next(p)
    assert b["tokens"].shape == (2, 2, 32)     # [m, B/m, T]


def test_determinism_same_seed():
    a = [np.asarray(next(mk(seed=7))["tokens"]) for _ in range(1)][0]
    b = np.asarray(next(mk(seed=7))["tokens"])
    np.testing.assert_array_equal(a, b)
    c = np.asarray(next(mk(seed=8))["tokens"])
    assert not np.array_equal(a, c)


def test_restore_resumes_stream():
    p1 = mk(seed=3)
    for _ in range(4):
        next(p1)                       # advance the stream
    state = p1.state()
    after = [np.asarray(next(p1)["tokens"]) for _ in range(2)]
    p2 = mk(seed=3)
    p2.restore(state)
    resumed = [np.asarray(next(p2)["tokens"]) for _ in range(2)]
    for a, b in zip(after, resumed):
        np.testing.assert_array_equal(a, b)


def test_packing_no_pads_and_eos_present():
    corpus = SyntheticCorpus(512, PipelineConfig(seed=0, mean_doc_len=20))
    b = PackedBatcher(corpus, 64)
    rows = b.next_rows(8)
    assert rows.shape == (8, 64)
    assert (rows != 0).all()                  # fully packed, no pad token
    assert (rows == 1).any()                  # eos separators present


def test_prefetch_thread():
    p = mk(seed=1).start()
    try:
        xs = [next(p) for _ in range(3)]
        assert len(xs) == 3
    finally:
        p.stop()


def test_prefetch_thread_hands_out_every_batch_in_order():
    want = tokens(mk(seed=2), 4)
    p = mk(seed=2, prefetch=1).start()
    try:
        got = []
        for _ in range(4):
            got.extend(tokens(p, 1))
            time.sleep(0.3)            # a consumer slower than the producer
    finally:
        p.stop()
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)


def test_prefetch_state_is_the_consumer_cursor():
    want = tokens(mk(seed=4), 5)
    p = mk(seed=4).start()
    try:
        tokens(p, 3)
        time.sleep(0.2)                # let the producer run ahead
        state = p.state()
    finally:
        p.stop()
    q = mk(seed=4)
    q.restore(state)
    for a, b in zip(want[3:], tokens(q, 2)):
        np.testing.assert_array_equal(a, b)


def test_restore_on_a_running_pipeline_drops_the_prefetched():
    want = tokens(mk(seed=5), 3)
    p = mk(seed=5).start()
    try:
        tokens(p, 1)
        state = p.state()
        tokens(p, 2)
        time.sleep(0.2)
        p.restore(state)
        got = tokens(p, 2)
    finally:
        p.stop()
    for a, b in zip(want[1:], got):
        np.testing.assert_array_equal(a, b)


def test_vlm_batch_has_image_embeds():
    cfg = reduced_config("phi-3-vision-4.2b").replace(train_microbatches=1)
    shape = ShapeConfig("t", "train", 32, 2)
    p = DataPipeline(cfg, shape, PipelineConfig(seed=0))
    b = next(p)
    assert b["image_embeds"].shape == (1, 2, cfg.image_tokens, cfg.d_model)
    assert b["tokens"].shape == (1, 2, 32 - cfg.image_tokens)
