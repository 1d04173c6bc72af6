"""Model FLOP utilization of the window, in percent: ``bench/flops.py``'s
FLOPs per step x steps / (window seconds x chips x the chip's bf16 peak)."""


def read(run):
    if not run.steps or run.peak_flops is None:
        return None
    return 100.0 * run.flops_per_step * run.steps / (
        run.window_s * run.chips * run.peak_flops)
