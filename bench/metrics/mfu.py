"""Model FLOP utilization of the window, in percent: the family's FLOPs per
step (``step_flops`` of ``bench/families/<family>.py``) x steps / (window
seconds x chips x the chip's bf16 peak)."""


def read(run):
    if not run.steps or run.peak_flops is None:
        return None
    return 100.0 * run.flops_per_step * run.steps / (
        run.window_s * run.chips * run.peak_flops)
