"""Device time per window step of the train-step program (its events on
the trace's ``XLA Modules`` line), averaged over the cell's chips."""
from bench import trace as T


def read(run):
    if run.trace is None or not run.steps:
        return None
    per_chip = [T.module_s(dev, run.step_module, run.trace_window)
                for dev in run.trace.devices.values()]
    if not any(per_chip):
        return None
    return 1e3 * sum(per_chip) / len(per_chip) / run.steps
