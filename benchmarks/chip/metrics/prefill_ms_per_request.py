"""Prefill: the engine's own ``admission`` spans in the window, summed,
over the requests admitted in the window."""


def read(run):
    n = sum(s.first_tokens for s in run.steps)
    if run.admission_s is None or not n:
        return None
    return run.admission_s / n * 1e3
