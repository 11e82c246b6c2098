"""Decode step: the engine's decode timer (host clock around the compiled
step and its ``block_until_ready``) over the window's decode steps."""


def read(run):
    dec = [s for s in run.steps if s.decode_tokens]
    if not dec:
        return None
    return sum(s.decode_s for s in dec) / len(dec) * 1e3
