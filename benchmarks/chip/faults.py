"""Faults planted under the timed path, to show that ``correct`` catches
them: ``run.py --fault <name>`` wraps the program's compiled decode step
(``system.wrap_decode_step``) before the engine is built.  For setting
and testing limits only; the benchmark's own runs plant nothing.

    altered_token    the step's logits shifted by one token, so every
                     decode token is altered where it is produced
    state_unchanged  the step returns the KV pools it was given
    half_batch       the second half of the batch gets slot 0's logits

A one-chip cell has no exchange between chips to leave out.
"""
from __future__ import annotations


def _altered_token(step):
    import jax.numpy as jnp

    def broken(*a, **k):
        logits, pools = step(*a, **k)
        return jnp.roll(logits, 1, axis=-1), pools
    return broken


def _state_unchanged(step):
    def broken(cfg, params, pools, *a, **k):
        logits, _ = step(cfg, params, pools, *a, **k)
        return logits, pools
    return broken


def _half_batch(step):
    def broken(*a, **k):
        logits, pools = step(*a, **k)
        half = logits.shape[0] // 2
        return logits.at[half:].set(logits[:1]), pools
    return broken


FAULTS = {"altered_token": _altered_token, "state_unchanged": _state_unchanged,
          "half_batch": _half_batch}
