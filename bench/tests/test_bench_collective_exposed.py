"""``collective_exposed_ms``: the collective time under no other op, on
hand-made events and on the trace recorded on four TPU v5e chips
(``data/tp4_probe.xplane.pb``, a small sharded step with an all-reduce)."""
import importlib.util
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import trace as T

BENCH = Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data" / "tp4_probe.xplane.pb"


def dev(*ops):
    return T.Device([T.Op(n, c, s, d) for n, c, s, d in ops], [])


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("ops,want", [
    # under a compute op: hidden
    ([("f", "fusion", 0.0, 4.0), ("ar", "all-reduce-start", 1.0, 2.0)], 0.0),
    # beside it: its own length
    ([("f", "fusion", 0.0, 1.0), ("ar", "all-reduce", 1.0, 2.0)], 2.0),
    # half under one op, half bare, the window cutting a second collective
    ([("f", "fusion", 0.0, 2.0), ("ag", "all-gather", 1.0, 2.0),
      ("cp", "collective-permute", 9.0, 3.0)], 2.0),
    # a loop's event spans its body: it hides nothing
    ([("while.1", "while", 0.0, 10.0), ("ar", "all-reduce", 2.0, 1.0)], 1.0),
    # no collective
    ([("f", "fusion", 0.0, 1.0)], 0.0),
])
def test_exposed_collective_time_on_hand_made_events(ops, want):
    total, bare = reader("collective_exposed_ms").split_s(
        dev(*ops), (0.0, 10.0))
    assert bare == pytest.approx(want) and bare <= total


@pytest.fixture(scope="module")
def recorded():
    return T.load(DATA)


def test_exposed_collective_time_on_the_recorded_trace(recorded):
    exposed, every = reader("collective_exposed_ms"), reader("collective_ms")
    w = (recorded.spans[0][1], recorded.spans[-1][2])
    for d in recorded.devices.values():
        total, bare = exposed.split_s(d, w)
        assert total == pytest.approx(T.collective_s(d, w)) and total > 0
        assert 0 <= bare <= total
        # v5e's XLA Ops line is serial: nothing runs under a synchronous
        # all-reduce, so all of it is exposed
        assert bare == pytest.approx(total)
    run = SimpleNamespace(trace=recorded, trace_window=w, steps=4)
    assert 0 <= exposed.read(run) <= every.read(run)


def test_exposed_collective_time_is_silent_without_a_trace_or_collective():
    exposed = reader("collective_exposed_ms")
    assert exposed.read(SimpleNamespace(trace=None, steps=4)) is None
    quiet = T.Trace({0: dev(("f", "fusion", 0.0, 1.0))}, [])
    assert exposed.read(SimpleNamespace(trace=quiet, trace_window=(0.0, 2.0),
                                        steps=1)) is None
