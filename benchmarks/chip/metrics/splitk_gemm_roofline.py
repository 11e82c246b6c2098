"""Kernels: the ``splitk_gemm`` kernel's least time over its device time
in the traced decode steps, in %.  Least time is summed call by call (the
step's tiered weight GEMMs at the active batch, published shapes); device time
is the sum of the kernel's events in the trace."""
import work
import xplane

KERNEL = "splitk_gemm"


def read(run):
    if run.trace is None:
        return None
    by_i = {s.i: s for s in run.trace_steps if s.decode_tokens}
    spans = [sp for sp in run.trace.spans
             if sp.name == "step" and int(sp.args.get("i", -1)) in by_i]
    t = xplane.op_time_in(run.trace, {KERNEL}, spans)
    if t <= 0:
        return None
    least = sum(work.gemm_least_time(run.arch, run.model,
                                     by_i[int(sp.args["i"])].decode_tokens, run.peak)
                for sp in spans)
    return 100.0 * least / t
