"""Mean wall time of the window's engine steps that carry no prefill
chunk (host clock around InferenceEngine.step)."""


def read(run):
    ts = [s.t1 - s.t0 for s in run.window_steps if s.chunk is None]
    return 1e3 * sum(ts) / len(ts) if ts else None
