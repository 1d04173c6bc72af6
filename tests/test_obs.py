"""The program's own measurement (``repro.runtime.obs``): host spans in
memory and in the profiler's trace, the compile log, and the input
pipeline's spans."""
import threading

import jax
import numpy as np
import pytest

from repro.configs import ShapeConfig, reduced_config
from repro.runtime import obs
from repro.runtime.pipeline import DataPipeline, PipelineConfig


@pytest.fixture(autouse=True)
def fresh():
    obs.reset()
    yield
    obs.reset()


def host_events(path, name):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    return [e for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events if e.name == name]


def test_span_is_kept_and_lands_once_in_the_trace(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("obs.test.span"):
            pass
    finally:
        jax.profiler.stop_trace()
    (start, end, thread), = obs.spans("obs.test.span")
    assert 0 <= end - start < 1e9
    assert thread == threading.get_ident()
    files = sorted(tmp_path.rglob("*.xplane.pb"))
    assert files
    assert len(host_events(files[-1], "obs.test.span")) == 1


def test_span_ring_is_bounded():
    for _ in range(obs.RING + 10):
        with obs.span("obs.test.ring"):
            pass
    assert len(obs.spans("obs.test.ring")) == obs.RING
    assert len(obs.spans("obs.test.never")) == 0


def test_a_fresh_jit_logs_one_compile_and_a_cached_call_none():
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    x = np.ones(7, np.float32)
    f(x).block_until_ready()
    assert len(obs.compiles()) == 1
    event, end_ns, seconds = obs.compiles()[0]
    assert event == obs.COMPILE_EVENT and end_ns > 0 and seconds >= 0
    f(x).block_until_ready()
    assert len(obs.compiles()) == 1


@pytest.mark.parametrize("threaded", [False, True])
def test_pipeline_spans_one_wait_and_one_put_per_batch(threaded):
    cfg = reduced_config("yi-6b").replace(train_microbatches=2)
    p = DataPipeline(cfg, ShapeConfig("t", "train", 32, 4),
                     PipelineConfig(seed=1, prefetch=2))
    if threaded:
        p.start()
    try:
        for _ in range(3):
            next(p)
    finally:
        p.stop()
    waits, puts = obs.spans("pipeline.wait"), obs.spans("pipeline.put")
    assert len(waits) == len(puts) == 3
    me = threading.get_ident()
    for (w0, w1, wt), (p0, p1, pt) in zip(waits, puts):
        assert w0 <= w1 <= p0 <= p1          # the wait, then the put
        assert wt == pt == me                 # both on the consumer


CACHE_FLAGS = ("jax_compilation_cache_dir",
               "jax_persistent_cache_min_compile_time_secs",
               "jax_compilation_cache_include_metadata_in_key")


@pytest.fixture
def compile_cache(tmp_path, monkeypatch):
    """The program's persistent compile cache, in ``tmp_path``; JAX's
    settings restored after."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.launch import __main__ as launcher
    saved = {k: getattr(jax.config, k) for k in CACHE_FLAGS}
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(launcher, "COMPILE_CACHE", tmp_path)
    cc.reset_cache()
    try:
        launcher.use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        yield tmp_path
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_a_program_from_the_compile_cache_keeps_its_own_names(compile_cache):
    def named(scope):
        def f(x):
            with jax.named_scope(scope):
                return jax.numpy.sin(x) * 2.0
        return f

    x = np.ones(4, np.float32)
    jax.jit(named("first_version")).lower(x).compile()
    assert any(compile_cache.iterdir())            # it was cached
    text = jax.jit(named("second_version")).lower(x).compile().as_text()
    assert "second_version" in text and "first_version" not in text
