"""Device time per window step of the step program's ``attention`` scope: the
attention block (projections, rope, scores, output projection), in all
phases (forward, backward, remat's recompute). ``bench/scopes.py`` names
the trace's ops by the compiled module."""
from bench import scopes


def read(run):
    return scopes.part_ms(run, "attention")
