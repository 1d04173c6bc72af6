"""Mean host time per window step that ``DataPipeline.__next__`` waits for
a built batch (the program's ``pipeline.wait`` span: the prefetch queue's
get, or the build itself when no thread runs)."""
from bench import inside


def read(run):
    return inside.mean_ms(run, "pipeline.wait")
