"""Device time per window step of the step program's ``embed`` scope: the
embedding gather and its scatter-add gradient, in all phases (forward,
backward, remat's recompute). ``bench/scopes.py`` names the trace's ops by
the compiled module."""
from bench import scopes


def read(run):
    return scopes.part_ms(run, "embed")
