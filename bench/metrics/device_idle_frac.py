"""Share of the traced window in which no operation ran on the device:
1 - (union of busy intervals / window), averaged over the cell's chips."""


def read(run):
    if run.trace is None or not run.busy_s:
        return None
    return 1.0 - sum(run.busy_s) / len(run.busy_s) / run.trace_window_s
