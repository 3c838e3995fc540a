"""Output tokens received in the window, divided by the window."""

from chipbench import reduce


def read(run):
    return reduce.tokens_in_window(run) / (run.w1 - run.w0)
