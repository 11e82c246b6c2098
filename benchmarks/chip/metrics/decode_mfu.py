"""Decode step: the chip's least time for the window's decode steps over
their measured time, in %.  Each step's least time is the larger of its
operations at peak FLOP/s and its bytes (weights once, the KV it reads
and writes) at HBM bandwidth, at the published shapes and the active
batch; for decode the bandwidth bound is the larger one, so this is the
step's share of its memory roofline."""
import work


def read(run):
    dec = [s for s in run.steps if s.decode_tokens]
    t = sum(s.decode_s for s in dec)
    if not dec or t <= 0:
        return None
    least = sum(work.least_time(*run.arch.decode_step(run.model, s), run.peak)[0]
                for s in dec)
    return 100.0 * least / t
