"""Measurement inside the program: host spans, a log of compiles, and the
compiled programs whose text names the parts of the step.

Always on, process-wide, and cheap: a span costs a few microseconds and
set-up pays a list append per program.

* ``span(name)`` is a ``jax.profiler.TraceAnnotation`` -- so it lands in
  any profile on the device trace's clock -- that also keeps
  ``(start_ns, end_ns, thread)`` on ``time.perf_counter_ns`` in a bounded
  ring per name, read back with ``spans(name)``.
* ``compiles()`` is the log of programs made, ``(event, end_ns, seconds)``:
  one entry per backend compile.  In JAX 0.9 that event wraps the
  persistent cache's lookup too, so a program loaded from the cache is
  logged once, like one compiled afresh.
* ``note_program(compiled)`` keeps a compiled executable;
  ``program_text(name)`` returns the optimized HLO text of the newest one
  whose module is ``name`` (``jit_train_step``), read only when asked.
"""
from __future__ import annotations

import collections
import threading
import time

import jax

RING = 4096                        # records kept per span name
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_rings: dict = {}                  # span name -> deque of (start, end, thread)
_compiles: collections.deque = collections.deque(maxlen=RING)
_noted: collections.deque = collections.deque(maxlen=8)   # newest last
_texts: dict = {}                  # HLO module name -> optimized HLO text


class _Span:
    __slots__ = ("name", "_ann", "_t0")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        ring = _rings.get(self.name)
        if ring is None:
            ring = _rings.setdefault(self.name,
                                     collections.deque(maxlen=RING))
        ring.append((self._t0, t1, threading.get_ident()))
        return False


def span(name: str) -> _Span:
    """``with span("pipeline.wait"): ...`` -- a host span named ``name``."""
    return _Span(name)


def spans(name: str) -> collections.deque:
    """The newest records of span ``name``, oldest first."""
    return _rings.get(name, collections.deque())


def compiles() -> collections.deque:
    """The newest ``(event, end_ns, seconds)`` of programs made."""
    return _compiles


def reset() -> None:
    """Forget every span and compile recorded so far."""
    _rings.clear()
    _compiles.clear()


def _on_duration(event: str, seconds: float, **_) -> None:
    if event == COMPILE_EVENT:
        _compiles.append((event, time.perf_counter_ns(), seconds))


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def note_program(compiled) -> None:
    """Keep ``compiled`` (a ``jax.stages.Compiled``) for ``program_text``."""
    _noted.append(compiled)


def program_text(name: str) -> str | None:
    """The optimized HLO text of the newest noted program whose module is
    ``name``, or None where none was noted."""
    while _noted:
        text = _noted.popleft().as_text()
        _texts[text.split(None, 2)[1].rstrip(",")] = text
    return _texts.get(name)
