"""Scheduler: mean active slots per decode step over the slots, in %."""


def read(run):
    dec = [s for s in run.steps if s.decode_tokens]
    if not dec:
        return None
    return 100.0 * sum(s.decode_tokens for s in dec) / len(dec) / run.slots
