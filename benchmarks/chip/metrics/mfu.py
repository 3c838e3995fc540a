"""Model FLOPs of every token the window's steps processed, over the
window times the chip's bf16 peak (%)."""

from chipbench import reduce


def read(run):
    return reduce.mfu(run, run.window_steps, run.w1 - run.w0)
