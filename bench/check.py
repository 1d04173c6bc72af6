"""The numbers that decide ``correct``: what the timed train step produced
against the float32 reference, on the same weights and batches.

* ``loss_gap.step1``, ``loss_gap.step2``: |loss - reference loss| of the
  first two steps (the second after one AdamW update).
* ``grad_gap``: over the leaves, the worst gap between the norm of the
  first gradient as the optimizer got it (clipped) and the reference's,
  over the larger of the reference leaf's norm and the median leaf's.
* ``change_gap``: the same for each leaf's change over the two steps.
  Leaves whose unclipped reference gradient is under a thousandth of the
  median leaf's are left out: there AdamW moves by round-off alone.
* ``tokens_mismatch``: tokens of the two batches the pipeline fed that
  differ from the benchmark's own stream (exact: limit 0).
* ``nonfinite_losses``: window steps whose loss is not finite (limit 0).
"""
from __future__ import annotations

import statistics

MOVE_FLOOR = 1e-3


def _worst(prog: dict, ref: dict, names) -> tuple:
    floor = statistics.median(ref.values())
    gaps = {n: abs(prog[n] - ref[n]) / max(ref[n], floor) for n in names}
    worst = max(gaps, key=lambda n: (gaps[n] != gaps[n], gaps[n]))
    return gaps[worst], worst


def readings(prog: dict, ref: dict) -> dict:
    """name -> (value, what it was read from)."""
    if set(prog["grad"]) != set(ref["grad"]):
        raise ValueError(f"leaves differ: {sorted(prog['grad'])} vs "
                         f"{sorted(ref['grad'])}")
    out = {}
    for i, (a, b) in enumerate(zip(prog["loss"], ref["loss"])):
        out[f"loss_gap.step{i + 1}"] = (abs(a - b), f"{a!r} vs {b!r}")
    g, leaf = _worst(prog["grad"], ref["grad"], ref["grad"])
    out["grad_gap"] = (g, f"leaf {leaf}: {prog['grad'][leaf]!r} vs "
                          f"{ref['grad'][leaf]!r}")
    floor = MOVE_FLOOR * statistics.median(ref["grad_raw"].values())
    moved = [n for n, v in ref["grad_raw"].items() if v >= floor]
    c, leaf = _worst(prog["change"], {n: ref["change"][n] for n in moved},
                     moved)
    out["change_gap"] = (c, f"leaf {leaf}: {prog['change'][leaf]!r} vs "
                            f"{ref['change'][leaf]!r}; {len(moved)} of "
                            f"{len(ref['change'])} leaves counted")
    return out


def judge(values: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}): every number at or under its
    limit; a number that is not a number fails."""
    checks, ok = {}, True
    for name, limit in limits.items():
        if name not in values:
            raise KeyError(f"no reading {name!r} for its limit")
        v = values[name]
        checks[name] = {"value": v, "limit": limit}
        ok = ok and v <= limit              # False for NaN
    return ok, checks
