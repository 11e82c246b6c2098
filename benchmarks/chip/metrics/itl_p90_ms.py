"""p90 of every gap between two consecutive tokens of a request, both
inside the window.  A decode token is stamped at the end of the engine
step that gave it, a first token at the engine's own stamp, so a step
that also runs prefills stretches the gap of every request it holds."""
from record import percentile


def read(run):
    gaps = []
    for r in run.requests:
        t = r.times
        gaps.extend(b - a for a, b in zip(t, t[1:]) if a >= run.t0 and b <= run.t1)
    p = percentile(gaps, 90)
    return None if p is None else p * 1e3
