"""Mean host time per window step spent waiting in ``next(pipe)`` for the
input pipeline's next batch (the benchmark's ``bench.input_wait`` span)."""


def read(run):
    if not run.input_wait_s:
        return None
    return 1e3 * sum(run.input_wait_s) / len(run.input_wait_s)
