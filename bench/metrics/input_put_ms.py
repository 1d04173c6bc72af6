"""Mean host time per window step that ``DataPipeline.__next__`` spends
handing the batch to the device (the program's ``pipeline.put`` span
around ``jax.device_put``)."""
from bench import inside


def read(run):
    return inside.mean_ms(run, "pipeline.put")
