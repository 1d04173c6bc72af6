"""Device time per window step of the step program's ops that no named
scope covers (norms, residual adds, loop bookkeeping, and any op the
compiled module does not name): what the scopes leave out of the step.
``bench/scopes.py`` names the trace's ops by the compiled module."""
from bench import scopes


def read(run):
    return scopes.part_ms(run, scopes.UNSCOPED)
