"""Device time per window step of the step program's ``grad_accum`` scope: the
float32 gradient accumulation over the microbatches, in all phases
(forward, backward, remat's recompute). ``bench/scopes.py`` names the
trace's ops by the compiled module."""
from bench import scopes


def read(run):
    return scopes.part_ms(run, "grad_accum")
