"""Programs compiled (or loaded from the persistent cache) inside the timed
window: the program's compile log, counted from the start of the window's
first ``pipeline.wait`` span for the window's length, both on
``time.perf_counter_ns``.  Should read 0: set-up warms every shape."""
from bench import inside


def read(run):
    recs = inside.window_spans(run, "pipeline.wait")
    if recs is None:
        return None
    t0 = recs[0][0]
    t1 = t0 + run.window_s * 1e9
    return sum(t0 <= end <= t1 for _, end, _ in inside.obs().compiles())
