"""Device time per window step of the collectives (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all), from the trace's
``XLA Ops`` line, averaged over the cell's chips.  Nothing where the
program runs none."""
from bench import trace as T


def read(run):
    if run.trace is None or not run.steps:
        return None
    per_chip = [T.collective_s(dev, run.trace_window)
                for dev in run.trace.devices.values()]
    if not any(per_chip):
        return None
    return 1e3 * sum(per_chip) / len(per_chip) / run.steps
