"""Device time per window step in which a collective ran and nothing else
did: per chip, the union of its collective ops' intervals in the window
less their overlap with the union of its other ops (a loop or call, whose
event spans its body, is not one of them), averaged over the cell's chips.
At most ``collective_ms``; nothing where the program runs no collective."""
from bench import trace as T


def _length(intervals, window) -> float:
    return sum(e - s for s, e in T.clip(T.union(intervals), window))


def split_s(dev, window) -> tuple:
    """(collective seconds, the part of them under no other op): |C \\ O|
    is |C u O| - |O|, held to [0, |C|] against round-off."""
    coll, other = [], []
    for o in dev.ops:
        if o.opcode in T.CONTAINERS:
            continue
        (coll if T.is_collective(o) else other).append(
            (o.start, o.start + o.dur))
    total = _length(coll, window)
    bare = _length(coll + other, window) - _length(other, window)
    return total, min(total, max(0.0, bare))


def read(run):
    if run.trace is None or not run.steps:
        return None
    split = [split_s(d, run.trace_window)
             for d in run.trace.devices.values()]
    if not any(total for total, _ in split):
        return None
    return 1e3 * sum(bare for _, bare in split) / len(split) / run.steps
