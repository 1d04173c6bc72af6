"""Pluggable kernel timing backends (DESIGN.md §12).

The measured-autotuning loop ranks candidate tiles by what the hardware
*does*, not what a closed-form cost model says it should do (the
supervised-scheduling thesis, arXiv:1909.03947).  One interface, two
implementations:

* :class:`WallClockBackend` — times the actual Pallas kernels
  (``kernels/matmul_blocked.py`` / ``kernels/flash_attention.py``) through
  the jit'd ``kernels/ops.py`` wrappers: interpret mode on the CPU,
  compiled on a TPU, warmup then median-of-k repeats, and result-vs-jnp-reference
  verification so a mis-tiled kernel can never report a fast-but-wrong
  time (a failed verification scores ``inf``).
* :class:`SimulatorBackend` — a deterministic seeded tile simulator in the
  spirit of the ragx systolic/simd pipelines: per-grid-step load /
  compute / writeback stages priced off the shared roofline vocabulary
  (``core/roofline.py``), VMEM-gated double buffering, a measured MXU
  efficiency droop on oversized tiles the analytic model misses, small-grid
  occupancy effects, and reproducible per-tile measurement noise keyed by
  ``blake2b(seed, case, tile)``.  CI runs on this backend, so the measured
  loop is byte-reproducible without hardware.

A measurement target is a :class:`KernelCase` — ``kernel`` ("matmul" or
"flash") plus the problem shape and dtype.  ``measure(case, tiles)``
returns seconds per candidate tile; callers (``core/kerneltune.py``) prune
infeasible tiles *before* calling, so a backend never spends wall clock on
a tile that cannot run.
"""
from __future__ import annotations

import dataclasses
import hashlib
import time

import numpy as np

from repro.core.roofline import V5E, Hardware, mxu_efficiency, roofline_time
from repro.kernels.flash_attention import vmem_bytes as fa_vmem
from repro.kernels.matmul_blocked import vmem_bytes as mm_vmem

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}

# Largest double-buffered working set (the kernels' ``vmem_bytes``) a tile
# may have.  The v5e compiler's scoped-VMEM limit is 16 MiB, and Mosaic adds
# scratch of its own (fp32 operand copies, the dot result) that the count
# leaves out.  Compiling the power-of-two tile cube (128..2048 a side) of
# the yi-6b ffn GEMM for a v5e: every matmul tile counted at or under
# 10.5 MiB compiled; at 12 MiB (1024, 256, 2048) was refused while
# (1024, 1024, 512) compiled, and above 13 MiB none did.  Every flash tile
# (d = 128) up to 12 MiB compiled, and the next size, 21 MiB, was refused.
# The budget admits only sizes at which every tile compiled.
VMEM_BUDGET = 11 * 2**20


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """One measurement target: which kernel, at which problem shape.

    ``matmul``: ``(m, k, n)`` GEMM, tiles are ``(block_m, block_n,
    block_k)``.  ``flash``: ``m`` = query length, ``n`` = key length,
    ``k`` = head dim, tiles are ``(block_q, block_k)``; ``batch`` and
    ``heads`` multiply the grid.  ``label`` carries provenance (e.g.
    ``"yi-6b/train_4k/ffn_up"``) into record meta — it is *not* part of
    the measurement identity, so zoo configs sharing a shape bucket share
    measurements."""
    kernel: str                   # "matmul" | "flash"
    m: int
    k: int
    n: int
    dtype: str = "bfloat16"
    batch: int = 1                # flash only
    heads: int = 1                # flash only
    causal: bool = True           # flash only
    label: str = ""

    @property
    def dtype_bytes(self) -> int:
        return DTYPE_BYTES[self.dtype]

    def key(self) -> tuple:
        """Measurement identity (label excluded): what LogStore memoized
        timings are keyed by, together with the backend name."""
        return (self.kernel, self.m, self.k, self.n, self.dtype,
                self.batch, self.heads, self.causal)


def tile_vmem_bytes(case: KernelCase, bm, bn, bk=None):
    """VMEM working set of one grid step, broadcast over tile arrays —
    the budget every feasibility mask checks before a tile is measured."""
    if case.kernel == "flash":
        return fa_vmem(bm, bn, case.k, case.dtype_bytes)
    return mm_vmem(bm, bn, bk, case.dtype_bytes)


def _noise(seed: int, case_key: tuple, tile: tuple, amp: float) -> float:
    """Deterministic per-(case, tile) multiplicative jitter in
    ``[1-amp, 1+amp]`` — the reproducible stand-in for run-to-run
    measurement variance."""
    h = hashlib.blake2b(repr((seed, case_key, tile)).encode(),
                        digest_size=8).digest()
    u = int.from_bytes(h, "big") / float(2**64 - 1)      # [0, 1]
    return 1.0 + amp * (2.0 * u - 1.0)


class SimulatorBackend:
    """Deterministic roofline-derived tile pipeline (see module docstring).

    Divergence from the closed-form cost model is the whole point: the
    simulator prices per-*step* tile traffic (not whole-matrix refetch),
    with loads overlapping compute (every tile it is given fits
    double-buffered: ``feasible_tiles`` prunes the rest before any
    backend is called), applies an MXU efficiency droop on tiles past 256x256
    (accumulate-pipeline pressure the analytic model ignores), charges a
    heavier per-step launch overhead, and perturbs every reading by a
    seeded +/-``noise_amp``.  Identical seeds give identical times."""

    name = "sim"
    deterministic = True

    # efficiency droop past a 256x256 output tile (log2(bm*bn) = 16) and
    # past bk = 256: accumulate-pipeline / VMEM-bank pressure the analytic
    # model does not price.  Calibrated so the simulated argmin lands one
    # exponent below the analytic argmin (~1.1x on large GEMMs) — the
    # measured-vs-modeled drift the paper's thesis turns on.
    DROOP_AREA = 0.45
    DROOP_K = 0.35

    def __init__(self, seed: int = 0, *, hw: Hardware = V5E,
                 noise_amp: float = 0.02, launch_s: float = 3e-7):
        self.seed = seed
        self.hw = hw
        self.noise_amp = noise_amp
        self.launch_s = launch_s
        self.measured = 0             # tiles timed, across all cases

    # ------------------------------------------------------------- matmul
    def _matmul_time(self, case: KernelCase, bm, bn, bk) -> float:
        db = case.dtype_bytes
        gm = -(-case.m // bm)
        gn = -(-case.n // bn)
        gk = -(-case.k // bk)
        steps = gm * gn * gk
        # steady-state step: tile loads vs MXU compute on the shared
        # roofline; oversized tiles droop (deep accumulate pipelines)
        eff = float(mxu_efficiency(bm, bn))
        droop = 1.0 + self.DROOP_AREA * max(0.0, np.log2(bm * bn) - 16.0) \
            + self.DROOP_K * max(0.0, np.log2(max(bk, 1)) - 8.0)
        load_bytes = (bm * bk + bk * bn) * db
        step = float(roofline_time(2.0 * bm * bn * bk * droop, load_bytes,
                                   hw=self.hw, eff=eff))
        fill = load_bytes / self.hw.hbm_bw
        writeback = gm * gn * bm * bn * db / self.hw.hbm_bw
        occupancy = 1.25 if steps < 4 else 1.0
        return (fill + steps * step) * occupancy + writeback \
            + steps * self.launch_s

    # -------------------------------------------------------------- flash
    def _flash_time(self, case: KernelCase, bq, bk) -> float:
        db = case.dtype_bytes
        d = case.k
        gq = -(-case.m // bq)
        gk = -(-case.n // bk)
        # causal masking skips ~half the (q, k) tile pairs on average
        live = 0.5 * (gk + 1) if case.causal else float(gk)
        eff = float(mxu_efficiency(bq, bk))
        droop = 1.0 + self.DROOP_AREA * max(0.0, np.log2(bq * bk) - 16.0)
        flops_step = (4.0 * bq * bk * d + 10.0 * bq * bk) * droop
        load_bytes = 2 * bk * d * db                      # K and V tiles
        step = float(roofline_time(flops_step, load_bytes, hw=self.hw,
                                   eff=eff))
        q_io = (bq * d * db) * 2 / self.hw.hbm_bw         # load q, store o
        row = q_io + live * step
        grid_rows = case.batch * case.heads * gq
        occupancy = 1.25 if grid_rows * gk < 4 else 1.0
        return grid_rows * row * occupancy \
            + grid_rows * live * self.launch_s

    # ---------------------------------------------------------- interface
    def measure(self, case: KernelCase, tiles) -> list[float]:
        """Seconds per candidate tile (``(bm, bn, bk)`` for matmul,
        ``(bq, bk)`` for flash).  Pure function of (seed, case, tile)."""
        out = []
        for tile in tiles:
            if case.kernel == "flash":
                t = self._flash_time(case, tile[0], tile[1])
            else:
                t = self._matmul_time(case, tile[0], tile[1], tile[2])
            out.append(t * _noise(self.seed, case.key(), tuple(tile),
                                  self.noise_amp))
            self.measured += 1
        return out


class WallClockBackend:
    """Times the real Pallas kernels: warmup, then median of ``reps``
    timed calls, each synchronized with ``block_until_ready``.  On the CPU
    the kernels run in interpret mode (slow but exact — keep cases small);
    on TPU they compile.  With ``verify=True`` every tile's output is
    checked against the jnp reference oracle first and a mismatch scores
    ``inf`` — a wrong result must never win the argmin.  ``platform`` and
    ``device_kind`` name the device the last ``measure`` timed on."""

    name = "wallclock"
    deterministic = False

    def __init__(self, *, reps: int = 3, warmup: int = 1,
                 verify: bool = True, atol: float = 2e-2, seed: int = 0):
        self.reps = reps
        self.warmup = warmup
        self.verify = verify
        self.atol = atol
        self.seed = seed
        self.measured = 0
        self.verified = 0
        self.verify_failures = 0
        self.platform = self.device_kind = None

    def _arrays(self, case: KernelCase):
        import jax.numpy as jnp
        rng = np.random.default_rng(self.seed)
        dt = jnp.float32 if case.dtype == "float32" else jnp.bfloat16
        if case.kernel == "flash":
            q = jnp.asarray(rng.normal(size=(case.batch, case.m, case.heads,
                                             case.k)), dt)
            kv_shape = (case.batch, case.n, case.heads, case.k)
            k = jnp.asarray(rng.normal(size=kv_shape), dt)
            v = jnp.asarray(rng.normal(size=kv_shape), dt)
            return q, k, v
        a = jnp.asarray(rng.normal(size=(case.m, case.k)), dt)
        b = jnp.asarray(rng.normal(size=(case.k, case.n)), dt)
        return a, b

    def _call(self, case: KernelCase, arrays, tile):
        from repro.kernels import ops
        if case.kernel == "flash":
            q, k, v = arrays
            return ops.flash_attention(q, k, v, causal=case.causal,
                                       block_q=int(tile[0]),
                                       block_k=int(tile[1]))
        a, b = arrays
        return ops.matmul(a, b, block_m=int(tile[0]), block_n=int(tile[1]),
                          block_k=int(tile[2]))

    def _reference(self, case: KernelCase, arrays):
        from repro.kernels.ref import flash_attention_ref, matmul_ref
        if case.kernel == "flash":
            q, k, v = arrays
            return flash_attention_ref(q, k, v, causal=case.causal)
        return matmul_ref(*arrays)

    def measure(self, case: KernelCase, tiles) -> list[float]:
        ref = self._reference(case, self._arrays(case)) if self.verify \
            else None
        arrays = self._arrays(case)
        dev = arrays[0].devices().pop()
        self.platform, self.device_kind = dev.platform, dev.device_kind
        out = []
        for tile in tiles:
            got = self._call(case, arrays, tile)
            got.block_until_ready()
            if ref is not None:
                ok = bool(np.allclose(np.asarray(got, np.float32),
                                      np.asarray(ref, np.float32),
                                      atol=self.atol, rtol=self.atol))
                if ok:
                    self.verified += 1
                else:
                    self.verify_failures += 1
                    out.append(float("inf"))
                    continue
            for _ in range(max(0, self.warmup - 1)):
                self._call(case, arrays, tile).block_until_ready()
            times = []
            for _ in range(self.reps):
                t0 = time.perf_counter()
                self._call(case, arrays, tile).block_until_ready()
                times.append(time.perf_counter() - t0)
            out.append(float(np.median(times)))
            self.measured += 1
        return out


_BACKENDS = {"sim": SimulatorBackend, "wallclock": WallClockBackend}


def get_backend(name: str, **kw):
    """Timing-backend registry: ``"sim"`` (deterministic, CI-safe) or
    ``"wallclock"`` (real kernels; interpret mode off-TPU)."""
    if name not in _BACKENDS:
        raise KeyError(f"unknown timing backend {name!r}; "
                       f"known: {sorted(_BACKENDS)}")
    return _BACKENDS[name](**kw)
