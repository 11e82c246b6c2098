"""Tokens emitted in the window (first tokens and decode tokens of every
request) over the window's seconds, idle waits included."""


def read(run):
    if run.window_s <= 0:
        return None
    return sum(s.decode_tokens + s.first_tokens for s in run.steps) / run.window_s
