"""Device time of the train step by named part of the model, and by phase.

The step program names its parts with ``jax.named_scope`` (``PARTS``).  The
compiled module's text keeps each instruction's name stack
(``metadata={op_name=...}``; a fusion keeps its root's), so the module read
back from the program (``repro.runtime.obs.program_text``) maps each
instruction to a part and a phase:

* part: the first of ``PARTS`` that is a component of the name stack, bare
  (``.../attention/dot_general``) or wrapped by a transformation
  (``transpose(jvp(head_loss))``); else ``unscoped``;
* phase: ``recompute`` under ``rematted_computation`` (remat's second
  forward), ``backward`` under ``transpose(``, else ``forward``.

The trace's device ops in the window are then summed by (part, phase), as
``trace.top_ops`` sums them (loops and calls left out, their body ops
counted); an op the module does not name is ``unscoped``.  Where the
program keeps no such text (it predates it), nothing is read.
"""
from __future__ import annotations

import functools
import re

from bench import inside, trace as T

PARTS = ("embed", "attention", "ffn", "head_loss", "grad_accum", "optimizer")
PHASES = ("forward", "backward", "recompute")
UNSCOPED = "unscoped"

_LINE = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=\s.*?op_name="([^"]*)"',
                   re.M)
_TOKEN = re.compile(r"[^/()]+")


def classify(op_name: str) -> tuple:
    """``(part, phase)`` of one instruction's name stack."""
    tokens = set(_TOKEN.findall(op_name))
    part = next((p for p in PARTS if p in tokens), UNSCOPED)
    if "rematted_computation" in tokens:
        phase = "recompute"
    elif "transpose(" in op_name:
        phase = "backward"
    else:
        phase = "forward"
    return part, phase


@functools.lru_cache(maxsize=2)
def table(hlo_text: str) -> dict:
    """``{instruction name: (part, phase)}`` of a module's HLO text."""
    return {m.group(1): classify(m.group(2))
            for m in _LINE.finditer(hlo_text)}


def program_table(module: str) -> dict | None:
    """The table of the program's compiled module ``module``, or None where
    the program keeps no text of it."""
    o = inside.obs()
    text = None if o is None else o.program_text(module)
    return None if text is None else table(text)


def device_ms(run) -> dict | None:
    """``{(part, phase): device ms per window step}``, averaged over the
    chips, for every part (``unscoped`` included) and phase; None without a
    trace or without the program's table."""
    if run.trace is None or not run.steps:
        return None
    names = program_table(run.step_module)
    if names is None:
        return None
    out = {(p, ph): 0.0 for p in PARTS + (UNSCOPED,) for ph in PHASES}
    for name, s in T.top_ops(run.trace, run.trace_window, n=None):
        out[names.get(name, (UNSCOPED, "forward"))] += 1e3 * s / run.steps
    return out


def part_ms(run, part: str) -> float | None:
    """Device ms per window step of ``part``, all phases."""
    ms = device_ms(run)
    return None if ms is None else sum(ms[(part, ph)] for ph in PHASES)


def phase_ms(run, phase: str) -> float | None:
    """Device ms per window step in ``phase``, all parts."""
    ms = device_ms(run)
    return None if ms is None else sum(
        ms[(p, phase)] for p in PARTS + (UNSCOPED,))
