"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

Read with ``jax.profiler.ProfileData``.  On a TPU the trace has one plane
per chip, ``/device:TPU:<n>``, whose ``XLA Ops`` line holds one event per
executed HLO instruction; the event's name is the instruction's text,
``%<name>.<id> = <shape> <opcode>(...)``, so a Pallas kernel appears under
its instruction name (``splitk_gemm``, ``paged_splitk_flashattn``).  Host
threads are on ``/host:CPU``, on the same time base.  There the
harness's own ``TraceAnnotation`` spans are named ``bench:<name>``, and the
program's are named likewise ``<namespace>:<name>`` (``engine:decode``); the
runtime's own host events are not (``PjitFunction(f)``, ``X::Y``,
``end: op``).

* busy: the union of a chip's op intervals inside the window, averaged
  over the chips;
* op time: each instruction's self time (its duration less the events it
  encloses, so a ``while`` does not count its body twice);
* idle gaps: the holes in the union, each labelled by the harness span
  that covers its middle.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench:"
PROGRAM_SPAN = re.compile(r"^[A-Za-z_][\w.\-]*:[^\s:]")
_INSTR = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?\s*=")


def instruction(name: str) -> str:
    """The instruction name without its numeric suffix:
    ``'%splitk_gemm.274 = bf16[..] custom-call(..)'`` -> ``'splitk_gemm'``."""
    m = _INSTR.match(name)
    return m.group(1) if m else name.split(" ", 1)[0]


@dataclasses.dataclass
class Op:
    name: str           # instruction name, suffix stripped
    start: float        # ns from the profile's start
    end: float


@dataclasses.dataclass
class Span:
    name: str           # without the ``bench:`` prefix
    start: float
    end: float
    args: dict


@dataclasses.dataclass
class Trace:
    devices: list[list[Op]]      # per chip, sorted by start
    spans: list[Span]            # harness spans, sorted by start
    # the program's annotations, every ``<namespace>:<name>`` host span but
    # the harness's, name in full, sorted by start with outer before inner
    program_spans: list[Span] = dataclasses.field(default_factory=list)


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans, program = [], [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops.extend(Op(instruction(e.name), e.start_ns, e.end_ns)
                               for e in line.events)
            ops.sort(key=lambda o: (o.start, -o.end))
            devices.append(ops)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Span(e.name[len(SPAN_PREFIX):], e.start_ns,
                                          e.end_ns, dict(e.stats)))
                    elif PROGRAM_SPAN.match(e.name):
                        program.append(Span(e.name, e.start_ns, e.end_ns,
                                            dict(e.stats)))
    spans.sort(key=lambda s: s.start)
    program.sort(key=lambda s: (s.start, -s.end))
    return Trace(devices, spans, program)


def merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def busy(ops: list[Op], lo: float, hi: float) -> list[tuple[float, float]]:
    return clip(merge([(o.start, o.end) for o in ops]), lo, hi)


def self_times(ops: list[Op], lo: float, hi: float) -> dict[str, float]:
    """Self time (ns) per instruction name, for ops starting in [lo, hi)."""
    out: dict[str, float] = {}
    stack: list[list] = []                     # [op, child time]
    for o in ops:
        while stack and stack[-1][0].end <= o.start:
            top, child = stack.pop()
            _add_self(out, top, child, lo, hi)
        if stack and o.end <= stack[-1][0].end:
            stack[-1][1] += o.end - o.start
        stack.append([o, 0.0])
    while stack:
        top, child = stack.pop()
        _add_self(out, top, child, lo, hi)
    return out


def _add_self(out, op, child, lo, hi):
    if lo <= op.start < hi:
        out[op.name] = out.get(op.name, 0.0) + max(0.0, op.end - op.start - child)


def window(trace: Trace) -> tuple[float, float]:
    """The traced window: from the first harness span's start to the last
    one's end."""
    if not trace.spans:
        raise ValueError("the trace holds no harness spans")
    return trace.spans[0].start, max(s.end for s in trace.spans)


def reduce(trace: Trace, label=lambda span: span.name, top: int = 10) -> dict:
    """Device numbers of the traced window.  ``label(span)`` names what
    the host was doing in a span.  Returns seconds throughout."""
    lo, hi = window(trace)
    if not trace.devices:
        raise ValueError("the trace holds no TPU device plane")
    busy_ns, op_ns, gaps = 0.0, {}, []
    for ops in trace.devices:
        iv = busy(ops, lo, hi)
        busy_ns += sum(b - a for a, b in iv)
        for k, v in self_times(ops, lo, hi).items():
            op_ns[k] = op_ns.get(k, 0.0) + v
        edges = [lo] + [x for ab in iv for x in ab] + [hi]
        gaps.extend((b - a, (a + b) / 2)
                    for a, b in zip(edges[0::2], edges[1::2]) if b > a)
    n = len(trace.devices)
    gaps.sort(key=lambda g: -g[0])
    ops_sorted = sorted(op_ns.items(), key=lambda kv: -kv[1])
    return {
        "busy_s": busy_ns / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[k, v / n / 1e9] for k, v in ops_sorted[:top]],
        "idle_gaps": [[_label(trace.spans, mid, label), g / 1e9]
                      for g, mid in gaps[:top]],
    }


def _label(spans: list[Span], t: float, label) -> str:
    inside = [s for s in spans if s.start <= t < s.end]
    if not inside:
        return "between-steps"
    return label(max(inside, key=lambda s: s.start))


def op_time_in(trace: Trace, names: set[str], spans: list[Span]) -> float:
    """Seconds of the named instructions inside ``spans``, averaged over
    the chips."""
    total = 0.0
    for ops in trace.devices:
        named = [o for o in ops if o.name in names]
        starts = [o.start for o in named]
        for s in spans:
            i, j = bisect.bisect_left(starts, s.start), bisect.bisect_left(starts, s.end)
            total += sum(o.end - o.start for o in named[i:j])
    return total / max(1, len(trace.devices)) / 1e9
