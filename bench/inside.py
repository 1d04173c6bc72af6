"""What the program records about itself (``repro.runtime.obs``: spans,
the compile log, the compiled step's text), read by the per-layer metrics.
A program that predates that module records nothing, and its readers read
nothing."""


def obs():
    """The program's ``repro.runtime.obs``, or None where it has none."""
    try:
        from repro.runtime import obs as program_obs
    except ImportError:
        return None
    return program_obs


def window_spans(run, name: str) -> list | None:
    """The window's ``(start_ns, end_ns, thread)`` records of the program's
    span ``name``: its last ``run.steps`` (one per window step; set-up's
    come before them and nothing after), or None where there are fewer."""
    o = obs()
    if o is None or not run.steps:
        return None
    recs = list(o.spans(name))[-run.steps:]
    return recs if len(recs) == run.steps else None


def mean_ms(run, name: str) -> float | None:
    """Mean length of the window's records of span ``name``, in ms."""
    recs = window_spans(run, name)
    if recs is None:
        return None
    return 1e-6 * sum(e - s for s, e, _ in recs) / len(recs)
