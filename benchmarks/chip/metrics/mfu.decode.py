"""Model FLOPs of the window's decode-only steps over their summed wall
time times the chip's bf16 peak (%)."""

from chipbench import reduce


def read(run):
    ss = [s for s in run.window_steps if s.chunk is None]
    return reduce.mfu(run, ss, sum(s.t1 - s.t0 for s in ss))
