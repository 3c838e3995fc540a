"""Share of the window's prompt tokens served from the prefix cache:
hits / (hits + tokens prefilled), from the engine's counters after the
steps before and at the end of the window."""


def read(run):
    ws = run.window_steps
    before = [s for s in run.steps if s.t1 <= run.w0]
    if not ws or not before:
        return None
    hits = ws[-1].hit_tokens - before[-1].hit_tokens
    pre = ws[-1].prefill_tokens - before[-1].prefill_tokens
    return 100.0 * hits / (hits + pre) if hits + pre else None
