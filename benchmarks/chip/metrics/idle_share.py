"""Share of the traced slice in which no operation ran on the device
(%): 1 - busy / slice, busy being the union of the device's operations."""

from chipbench import trace


def read(run):
    if run.trace is None or not run.trace.device_ops:
        return None
    s = trace.summary(run.trace, run.trace_lo, run.trace_hi)
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
