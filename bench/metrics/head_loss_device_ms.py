"""Device time per window step of the step program's ``head_loss`` scope: the
vocabulary head and the cross-entropy, in all phases (forward, backward,
remat's recompute). ``bench/scopes.py`` names the trace's ops by the
compiled module."""
from bench import scopes


def read(run):
    return scopes.part_ms(run, "head_loss")
