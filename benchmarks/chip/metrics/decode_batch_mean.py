"""Mean number of decode rows per step in the window, from the
scheduler's plans."""


def read(run):
    ws = run.window_steps
    return sum(len(s.decode_ctxs) for s in ws) / len(ws) if ws else None
