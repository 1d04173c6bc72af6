"""Device time per window step of the step program's ``optimizer`` scope: the
optimizer update, its clipping norm and the per-leaf gradient norms, in all
phases (forward, backward, remat's recompute). ``bench/scopes.py`` names
the trace's ops by the compiled module."""
from bench import scopes


def read(run):
    return scopes.part_ms(run, "optimizer")
