"""round_step_mfu (%): the model FLOPs the window's rounds require (two
forward + backward passes per example and local step, one more per new
client's Psi) over the window's seconds, over chips x the chip's peak."""


def read(run):
    if run["window_s"] <= 0 or run["model_flops"] <= 0:
        return None
    return 100.0 * run["model_flops"] / run["window_s"] / (
        run["chips"] * run["peak"]["flops_per_s"])
