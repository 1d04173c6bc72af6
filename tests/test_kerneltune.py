"""Measured kernel autotuning (core/kerneltune.py + kernels/timing.py):
feasibility masks, the prune-before-measure contract, memoization, the
(bm, bn, bk) cascade, and serving-path parity."""
import dataclasses
import math

import numpy as np
import pytest

from repro.core.kerneltune import (DEFAULT_BK, MEASURED_SOURCE, VMEM_BUDGET,
                                   KernelQuery, KernelTuner,
                                   KernelTunerService, bucket_case,
                                   bucket_pow2, build_training_log,
                                   candidate_tiles, case_features,
                                   default_tile, feasible_tiles,
                                   flash_tile_times, matmul_tile_times,
                                   measure_case, measure_cases, measured_env,
                                   prior_times, seed_tiles, shape_features,
                                   tile_algo)
from repro.core.log import ExecutionLog, ExecutionRecord
from repro.data.logstore import LogStore
from repro.kernels.flash_attention import vmem_bytes as fa_vmem
from repro.kernels.matmul_blocked import vmem_bytes as mm_vmem
from repro.kernels.timing import (KernelCase, SimulatorBackend, get_backend,
                                  tile_vmem_bytes)


# ------------------------------------------------------ feasibility masks
def test_matmul_mask_tile_exactly_at_budget_is_feasible():
    # mm_vmem(512, 512, 2304, db=2) = 4096*2304 + 2 MiB = 11 MiB = VMEM_BUDGET
    assert mm_vmem(512, 512, 2304, 2) == VMEM_BUDGET
    t_at = float(matmul_tile_times(4096, 4096, 4096, 512, 512, 2304))
    t_over = float(matmul_tile_times(4096, 4096, 4096, 512, 512, 2305))
    assert math.isfinite(t_at)            # mask is strict `> budget`
    assert math.isinf(t_over)             # one element over -> OOM


def test_flash_mask_tracks_its_vmem_formula():
    for bq, bk, d in [(128, 128, 128), (512, 2048, 128), (2048, 2048, 256)]:
        finite = math.isfinite(
            float(flash_tile_times(4096, d, 4096, bq, bk)))
        assert finite == (fa_vmem(bq, bk, d, 2) <= VMEM_BUDGET)


def test_mask_non_power_of_two_remainder_tiles():
    # 1536 = 1024 + 512 remainder; ceil grids must stay finite, overhang inf
    # (bn = 512: a (1024, 1024, 512) tile is over the VMEM budget)
    assert math.isfinite(
        float(matmul_tile_times(1536, 1536, 1536, 1024, 512, 512)))
    assert math.isinf(
        float(matmul_tile_times(1536, 1536, 1536, 2048, 1024, 512)))
    # non-pow2 tile itself (96 is not MXU-aligned but is legal)
    assert math.isfinite(
        float(matmul_tile_times(1024, 1024, 1024, 96, 96, 96)))


def test_mask_dtype_bytes_variants():
    # feasible in bf16, over budget in fp32: working set scales with db
    tile = (512, 512, 1536)
    assert mm_vmem(*tile, 2) <= VMEM_BUDGET < mm_vmem(*tile, 4)
    assert math.isfinite(float(matmul_tile_times(
        4096, 4096, 4096, *tile, dtype_bytes=2)))
    assert math.isinf(float(matmul_tile_times(
        4096, 4096, 4096, *tile, dtype_bytes=4)))

    bf16 = KernelCase("matmul", 4096, 4096, 4096)
    fp32 = dataclasses.replace(bf16, dtype="float32")
    assert tile in feasible_tiles(bf16, [tile])
    assert feasible_tiles(fp32, [tile]) == []
    assert tile_vmem_bytes(fp32, *tile) == mm_vmem(*tile, 4)


@pytest.mark.parametrize("tile", [(1024, 1024, 1024), (2048, 1024, 512),
                                  (2048, 512, 512), (1024, 256, 2048)])
def test_tiles_the_compiler_refuses_are_infeasible(tile):
    # the v5e compiler runs out of scoped VMEM on these bf16 tiles of the
    # yi-6b ffn GEMM (tests/test_tpu_compile.py); counting one buffer per
    # operand had admitted them
    case = KernelCase("matmul", 4096, 4096, 11008)
    assert feasible_tiles(case, [tile]) == []
    assert math.isinf(float(matmul_tile_times(4096, 4096, 11008, *tile)))


def test_feasible_tiles_budget_boundary_inclusive():
    case = KernelCase("flash", 4096, 128, 4096)
    tile = (512, 512)
    budget = fa_vmem(512, 512, 128, 2)
    assert feasible_tiles(case, [tile], budget=budget) == [tile]
    assert feasible_tiles(case, [tile], budget=budget - 1) == []


# ------------------------------------------- prune-before-measure contract
class _SpyBackend(SimulatorBackend):
    """Records every tile it is asked to time."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.seen = []

    def measure(self, case, tiles):
        self.seen.extend(tuple(t) for t in tiles)
        return super().measure(case, tiles)


def test_infeasible_tiles_never_reach_the_backend():
    case = KernelCase("matmul", 4096, 4096, 4096)
    oom = (2048, 2048, 2048)              # 32 MiB working set
    assert mm_vmem(*oom, 2) > VMEM_BUDGET
    spy = _SpyBackend()
    _, stats = measure_case(case, spy, tiles=[oom, (128, 128, 128)])
    assert oom not in spy.seen
    assert (128, 128, 128) in spy.seen
    assert stats["pruned"] == 1 and stats["measured"] == 1


def test_seed_tiles_are_feasible_ranked_and_capped():
    case = bucket_case(KernelCase("matmul", 4096, 4096, 4096))
    tiles = seed_tiles(case, max_pairs=4, bk_per_pair=2)
    assert 0 < len(tiles) <= 4 * 2
    assert len({(bm, bn) for bm, bn, _ in tiles}) <= 4
    assert feasible_tiles(case, tiles) == tiles
    # the shortlist leads with the analytic argmin over the feasible cube
    cube = feasible_tiles(case, candidate_tiles(case))
    prior = prior_times(case, cube)
    assert tiles[0] == cube[int(np.argmin(prior))]


def test_flash_seed_tiles_are_pairs():
    case = bucket_case(KernelCase("flash", 4096, 128, 4096))
    tiles = seed_tiles(case, max_pairs=3)
    assert 0 < len(tiles) <= 3
    assert all(len(t) == 2 for t in tiles)
    assert feasible_tiles(case, tiles) == tiles


# -------------------------------------------------- simulator determinism
def test_simulator_is_deterministic_per_seed():
    case = KernelCase("matmul", 2048, 2048, 2048)
    tiles = seed_tiles(bucket_case(case))
    a = SimulatorBackend(seed=7).measure(case, tiles)
    b = SimulatorBackend(seed=7).measure(case, tiles)
    c = SimulatorBackend(seed=8).measure(case, tiles)
    assert a == b
    assert a != c                         # noise is keyed by the seed
    assert all(t > 0 and math.isfinite(t) for t in a)


def test_get_backend_registry():
    assert isinstance(get_backend("sim", seed=3), SimulatorBackend)
    assert get_backend("sim", seed=3).seed == 3
    with pytest.raises(KeyError):
        get_backend("cycle_accurate")


# --------------------------------------------------- memoization in store
def test_measure_case_memoizes_in_logstore(tmp_path):
    store = LogStore(tmp_path / "kernel.jsonl")
    case = KernelCase("matmul", 4096, 4096, 4096, label="yi/train/ffn")
    recs1, st1 = measure_case(case, SimulatorBackend(seed=0), store)
    assert st1["measured"] > 0 and st1["cached"] == 0
    recs2, st2 = measure_case(case, SimulatorBackend(seed=0), store)
    assert st2["measured"] == 0
    assert st2["cached"] == len(recs1) == len(recs2)
    assert {(r.p_r, r.p_c) for r in recs1} == \
        {(r.p_r, r.p_c) for r in recs2}

    # the memo is the (kernel, m, k, n, dtype, backend) LogStore triple
    bcase = bucket_case(case)
    cells = store.group_cells(case_features(bcase),
                              tile_algo(bcase.kernel),
                              measured_env(bcase, SimulatorBackend()),
                              source=MEASURED_SOURCE)
    assert set(cells) == {(r.p_r, r.p_c) for r in recs1}
    assert all("bk" in r.meta for r in cells.values())

    # a different dtype is a different memo line -- nothing is reused
    _, st3 = measure_case(dataclasses.replace(case, dtype="float32"),
                          SimulatorBackend(seed=0), store)
    assert st3["measured"] > 0 and st3["cached"] == 0


def test_measure_cases_dedups_shape_buckets(tmp_path):
    store = LogStore(tmp_path / "kernel.jsonl")
    cases = [KernelCase("matmul", 1000, 4096, 4096, label="a"),
             KernelCase("matmul", 1024, 4096, 4096, label="b"),
             KernelCase("matmul", 2048, 4096, 4096, label="c")]
    _, stats = measure_cases(cases, SimulatorBackend(seed=0), store)
    assert stats["cases"] == 3
    assert stats["bucket_hits"] == 1      # 1000 and 1024 share a bucket
    assert bucket_pow2(1000) == 1024


# ------------------------------------------------ the (bm, bn, bk) cascade
def test_predict_returns_full_tile_with_learned_bk():
    tun = KernelTuner().fit(build_training_log(n_shapes=12))
    pred = tun.predict(4096, 4096, 4096)
    assert len(pred) == 3
    bm, bn, bk = pred
    assert all(v >= 1 and (v & (v - 1)) == 0 for v in pred)  # powers of two
    assert bk <= 4096
    assert tun._bk.clf is not None        # trained from grid-search meta


def test_predict_bk_falls_back_without_bk_evidence():
    # hand-built log whose records carry no bk meta: stage three abstains
    log = ExecutionLog()
    rng = np.random.default_rng(0)
    for _ in range(6):
        m = int(2 ** rng.integers(9, 13))
        n = int(2 ** rng.integers(9, 13))
        for bm in (128, 256):
            for bn in (128, 256):
                t = 1.0 / (bm * bn) + 1e-4 * (bm == 256)
                log.add(ExecutionRecord(shape_features(m, 1024, n),
                                        "matmul_tile", {"vmem_mb": 16},
                                        bm, bn, t))
    tun = KernelTuner().fit(log)
    assert tun._bk.clf is None
    assert tun.predict(2048, 2048, 2048)[2] == DEFAULT_BK
    # the fallback still clamps to the reduction dim
    assert tun.predict(2048, 64, 2048)[2] == min(DEFAULT_BK, 64)


def test_measured_fit_serves_measured_argmin(tmp_path):
    store = LogStore(tmp_path / "kernel.jsonl")
    case = KernelCase("matmul", 4096, 4096, 4096)
    recs, _ = measure_case(case, SimulatorBackend(seed=0), store)
    tun = KernelTuner().fit(
        store.load(algos="matmul_tile", source=MEASURED_SOURCE))
    best = min(recs, key=lambda r: r.time_s)
    bm, bn, bk = tun.predict(4096, 4096, 4096)
    assert (bm, bn) == (best.p_r, best.p_c)
    assert bk == best.meta["bk"]


def test_flash_tuner_predicts_pairs(tmp_path):
    store = LogStore(tmp_path / "kernel.jsonl")
    case = KernelCase("flash", 4096, 128, 4096, heads=16)
    recs, _ = measure_case(case, SimulatorBackend(seed=0), store)
    tun = KernelTuner("flash").fit(
        store.load(algos="flash_tile", source=MEASURED_SOURCE))
    pred = tun.predict(4096, 128, 4096)
    assert len(pred) == 2
    best = min(recs, key=lambda r: r.time_s)
    assert pred == (best.p_r, best.p_c)


# --------------------------------------------------- serving-path parity
def _measured_tuner(tmp_path):
    store = LogStore(tmp_path / "kernel.jsonl")
    cases = [KernelCase("matmul", int(m), int(k), int(n))
             for m in (1024, 4096) for k in (1024, 4096)
             for n in (1024, 4096)]
    measure_cases(cases, SimulatorBackend(seed=0), store)
    return KernelTuner().fit(
        store.load(algos="matmul_tile", source=MEASURED_SOURCE))


def test_service_parity_with_direct_predict(tmp_path):
    tun = _measured_tuner(tmp_path)
    svc = KernelTunerService(tun)
    queries = [KernelQuery(m, k, n)
               for m in (1024, 4096) for k in (1024, 4096)
               for n in (1024, 4096)]
    served = svc.predict_batch(queries)
    direct = tun.predict_batch([(q.m, q.k, q.n, q.dtype) for q in queries])
    assert served == direct               # pow2 shapes: no clamp, no drift
    # warm pass answers from the bucket memo, identically
    assert svc.predict_batch(queries) == served
    assert svc.hits >= len(queries)
    # non-pow2 query shares its bucket's prediction, clamped to the shape
    odd = KernelQuery(1000, 4096, 4096)
    bm, bn, bk = svc.predict(odd)
    assert (min(bm, 1000), bn, bk) == (bm, bn, bk)
    ref = tun.predict(1024, 4096, 4096)
    assert (bm, bn, bk) == (min(ref[0], 1000), min(ref[1], 4096),
                            min(ref[2], 4096))


def test_router_serves_kernel_tiles(tmp_path):
    from repro.serve.router import ShardRouter
    tun = _measured_tuner(tmp_path)
    router = ShardRouter(tun, n_shards=2,
                         service_factory=KernelTunerService,
                         abstain_fallback=default_tile)
    try:
        q = KernelQuery(4096, 4096, 4096)
        assert router.predict(q) == tun.predict(4096, 4096, 4096)
        # unknown algo -> abstain fallback, not a crash
        flash_q = KernelQuery(4096, 128, 4096, algo="flash_tile")
        assert router.predict(flash_q) == default_tile(flash_q)
    finally:
        router.close()


# --------------------------------------------------------- full zoo sweep
@pytest.mark.slow
def test_full_zoo_measured_sweep_beats_cost_model():
    """The headline over every eval shape of the configs/ zoo (the smoke
    bench runs a reduced slice; nightly runs this)."""
    from repro.eval.harness import evaluate_kernels
    report = evaluate_kernels(backend=SimulatorBackend(seed=0))
    overall = report["overall"]
    assert report["config"]["n_configs"] >= 10
    assert overall["beat_costmodel_frac"] > 0.5, overall
    assert overall["geomean_speedup_vs_costmodel"] > 1.0, overall
    assert overall["mean_regret_vs_best"] < 1.1, overall
