"""Device time per window step of remat's second forward (ops under
``rematted_computation``), every part of the step program together.
``bench/scopes.py`` names the trace's ops by the compiled module."""
from bench import scopes


def read(run):
    return scopes.phase_ms(run, "recompute")
