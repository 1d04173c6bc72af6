"""The trace reduction, on hand-made events and on a trace recorded on four
TPU v5e chips (a small sharded step with an all-reduce, four steps, the
benchmark's host spans around each call).  Reads the file with JAX's own
reader; loads no TPU library."""
from pathlib import Path

import pytest

from bench import trace as T

DATA = Path(__file__).resolve().parent / "data" / "tp4_probe.xplane.pb"


def dev(*ops, modules=()):
    return T.Device([T.Op(n, c, s, d) for n, c, s, d in ops], list(modules))


def test_union_merges_overlaps_and_touching():
    assert T.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]


def test_busy_and_idle_share_in_window():
    d = dev(("a", "fusion", 1.0, 2.0), ("b", "fusion", 2.0, 1.0),
            ("c", "fusion", 6.0, 1.0), ("d", "fusion", 9.5, 2.0))
    w = (0.0, 10.0)
    assert T.busy_s(d, w) == pytest.approx(2.0 + 1.0 + 0.5)
    assert T.idle_gaps(d, w) == [(0.0, 1.0), (3.0, 6.0), (7.0, 9.5)]
    assert 1 - T.busy_s(d, w) / 10.0 == pytest.approx(0.65)


def test_module_time_is_the_step_programs_only():
    d = dev(modules=[("jit_train_step(123)", 0.0, 2.0),
                     ("jit_train_step(123)", 3.0, 2.0),
                     ("jit_other(9)", 5.0, 1.0),
                     ("jit_train_step_v2(1)", 6.0, 1.0)])
    assert T.module_s(d, "jit_train_step", (0.0, 4.0)) == pytest.approx(3.0)


@pytest.mark.parametrize("text,name,opcode,coll", [
    ("%all-reduce.3 = bf16[512,1024]{1,0} all-reduce(bf16[512,1024]{1,0} "
     "%fusion.8), channel_id=1", "all-reduce.3", "all-reduce", True),
    ("%fusion.8 = bf16[512,1024]{1,0} fusion(bf16[512,512]{1,0} %x), "
     "kind=kOutput", "fusion.8", "fusion", False),
    ("%ag = (f32[8], f32[32]) all-gather-start(f32[8] %p)", "ag",
     "all-gather-start", True),
    ("%cp.1 = f32[8] collective-permute(f32[8] %p)", "cp.1",
     "collective-permute", True),
    ("reduce-scatter.2", "reduce-scatter.2", "reduce-scatter", True),
    ("copy.4", "copy.4", "copy", False),
])
def test_op_names_and_collectives(text, name, opcode, coll):
    n, c = T.parse_op_name(text)
    assert (n, c) == (name, opcode)
    assert T.is_collective(T.Op(n, c, 0.0, 1.0)) is coll


def test_gaps_go_to_the_host_span_they_overlap_most():
    gaps = [(0.0, 1.0), (4.0, 6.0), (7.0, 7.1)]
    spans = [("bench.input_wait", 3.5, 5.8), ("bench.loss_read", 5.8, 6.5),
             ("bench.dispatch", 0.9, 1.0)]
    assert T.attribute_gaps(gaps, spans) == [
        ["input_wait", 2.0], ["dispatch", 1.0], ["other", pytest.approx(0.1)]]


@pytest.fixture(scope="module")
def recorded():
    return T.load(DATA)


def test_recorded_trace_has_four_chips_and_the_benchmark_spans(recorded):
    assert sorted(recorded.devices) == [0, 1, 2, 3]
    names = [s[0] for s in recorded.spans]
    for what in ("input_wait", "dispatch", "loss_read"):
        assert names.count(f"bench.{what}") == 4


def test_recorded_trace_reduces_per_chip(recorded):
    w = (recorded.spans[0][1], recorded.spans[-1][2])
    for d in recorded.devices.values():
        busy = T.busy_s(d, w)
        assert 0 < busy < w[1] - w[0]
        # four steps of the one program, each with its one all-reduce
        assert len(d.modules) == 4
        assert T.module_s(d, "jit_step", w) > 0
        assert T.module_s(d, "jit_train_step", w) == 0
        coll = [o for o in d.ops if T.is_collective(o)]
        assert [o.opcode for o in coll] == ["all-reduce"] * 4
        assert 0 < T.collective_s(d, w) < busy
    top = T.top_ops(recorded, w, n=3)
    assert top[0][0] == "all-reduce" and len(top) == 3
    # the host slept inside bench.input_wait: the longest gaps are its
    gaps = T.attribute_gaps(T.idle_gaps(recorded.devices[0], w),
                            recorded.spans)
    assert gaps[0][0] == "input_wait"
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)


def test_span_window_is_first_start_to_last_end(recorded):
    s, e = T.span_window(recorded, "bench.dispatch")
    disp = [x for x in recorded.spans if x[0] == "bench.dispatch"]
    assert (s, e) == (disp[0][1], disp[-1][2])
    with pytest.raises(ValueError):
        T.span_window(recorded, "bench.window")


def test_top_ops_leave_out_loops_whose_body_ops_are_counted():
    tr = T.Trace({0: dev(("while.8", "while", 0.0, 3.0),
                         ("fusion.1", "fusion", 0.0, 2.0),
                         ("fusion.2", "fusion", 2.0, 1.0)),
                  1: dev(("while.8", "while", 0.0, 3.0),
                         ("fusion.1", "fusion", 0.0, 1.0))}, [])
    assert T.top_ops(tr, (0.0, 10.0)) == [["fusion.1", 1.5],
                                          ["fusion.2", 0.5]]
