"""Reduce a profiler trace (``.xplane.pb``) to device events and host spans,
and the arithmetic the per-layer metrics share.

All times are in seconds on the trace's own clock: host and device planes
share it.  A device's busy time is the union of its ``XLA Ops`` intervals;
modules (whole programs) are on the ``XLA Modules`` line.  Host spans are
the benchmark's own ``TraceAnnotation``s, named ``bench.<what>``.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIX = "bench."
#: HLO opcodes that move data between chips
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
#: ops whose event spans the ops of their body, which have events too
CONTAINERS = ("while", "conditional", "call")


@dataclass
class Op:
    name: str          # the HLO instruction's name, without '%'
    opcode: str        # the HLO opcode ('fusion', 'all-reduce-start', ...)
    start: float
    dur: float


@dataclass
class Device:
    ops: list = field(default_factory=list)        # [Op]
    modules: list = field(default_factory=list)    # [(name, start, dur)]


@dataclass
class Trace:
    devices: dict          # device id -> Device
    spans: list            # [(name, start, end)] host spans, bench.* only


_INSTR = re.compile(r"^%?([^\s=]+)\s*=\s*.*?\s([a-z][a-z0-9\-_.]*)\(")


def parse_op_name(text: str) -> tuple:
    """``'%all-reduce.3 = bf16[..] all-reduce(...)'`` -> (name, opcode).
    A bare name (no HLO text) is its own opcode, its numeric suffix cut."""
    m = _INSTR.match(text)
    if m:
        return m.group(1), m.group(2)
    name = text.lstrip("%").split(" ")[0]
    return name, re.sub(r"\.\d+$", "", name)


def is_collective(op: Op) -> bool:
    return any(op.opcode.startswith(c) or op.name.startswith(c)
               for c in COLLECTIVES)


def load(path) -> Trace:
    """Read ``path`` with JAX's own reader: device ops and modules of every
    TPU plane, and the host's ``bench.*`` spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, spans = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), Device())
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        name, opcode = parse_op_name(e.name)
                        dev.ops.append(Op(name, opcode, e.start_ns * 1e-9,
                                          e.duration_ns * 1e-9))
                elif line.name == "XLA Modules":
                    dev.modules.extend(
                        (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    (e.name, e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    spans.sort(key=lambda s: s[1])
    return Trace(devices, spans)


# --------------------------------------------------------------- arithmetic

def union(intervals) -> list:
    """Merge ``[(start, end)]`` into disjoint, sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, window) -> list:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_intervals(dev: Device, window) -> list:
    return clip(union((o.start, o.start + o.dur) for o in dev.ops), window)


def busy_s(dev: Device, window) -> float:
    return sum(e - s for s, e in busy_intervals(dev, window))


def idle_gaps(dev: Device, window) -> list:
    """The intervals of ``window`` in which no op ran on ``dev``."""
    gaps, t = [], window[0]
    for s, e in busy_intervals(dev, window):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < window[1]:
        gaps.append((t, window[1]))
    return gaps


def module_s(dev: Device, prefix: str, window) -> float:
    """Device seconds of the modules whose name starts with ``prefix``
    (``jit_train_step`` matches ``jit_train_step(1234)``)."""
    return sum(e - s for s, e in clip(
        [(s, s + d) for n, s, d in dev.modules if n.startswith(prefix + "(")
         or n == prefix], window))


def collective_s(dev: Device, window) -> float:
    return sum(e - s for s, e in clip(
        union((o.start, o.start + o.dur) for o in dev.ops
              if is_collective(o)), window))


def top_ops(trace: Trace, window, n=10) -> list:
    """``[[name, seconds]]`` of the ops that took most device time, summed
    over the window and averaged over the devices; a loop or call is left
    out, as the ops of its body are counted."""
    tot = {}
    for dev in trace.devices.values():
        for o in dev.ops:
            if o.opcode in CONTAINERS:
                continue
            (s, e), = clip([(o.start, o.start + o.dur)], window) or [(0, 0)]
            tot[o.name] = tot.get(o.name, 0.0) + (e - s)
    k = max(len(trace.devices), 1)
    return [[name, t / k] for name, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def attribute_gaps(gaps, spans, n=10) -> list:
    """The ``n`` longest gaps as ``[[what, seconds]]``: ``what`` is the host
    span that overlaps the gap most (its name without ``bench.``), or
    ``other`` where none does."""
    out = []
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        best, best_t = "other", 0.0
        for name, s, e in spans:
            t = min(e, g1) - max(s, g0)
            if t > best_t:
                best, best_t = name[len(SPAN_PREFIX):], t
        out.append([best, g1 - g0])
    return out


def span_window(trace: Trace, name: str) -> tuple:
    """First start and last end of the host spans called ``name``."""
    sel = [(s, e) for n, s, e in trace.spans if n == name]
    if not sel:
        raise ValueError(f"no host span {name!r} in the trace")
    return min(s for s, _ in sel), max(e for _, e in sel)
