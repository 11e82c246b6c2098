"""Device idle time by engine phase.

With a recorder, the serving engine opens each of its phases as a
``jax.profiler.TraceAnnotation`` named ``engine:<phase>`` (``step``,
``admission``, ``prefill``, ``prefill.sync``, ``kv_write``, ``decode``,
``decode.prep``, ``decode.wait``, ``finish``).  In a profile they are on
``/host:CPU``, on the same time base as the device ops and the harness's
``bench:`` spans, so every moment the chips sat idle can be booked to
what the host was doing then.

A phase is named by its path, the names of the engine spans over it from
the outermost in: ``step/admission/prefill``; ``outside`` where no
engine span is open.
"""
from __future__ import annotations

import xplane

PREFIX = "engine:"
OUTSIDE = "outside"


def engine_spans(trace: xplane.Trace) -> list[xplane.Span]:
    """The engine's spans among the trace's program spans, prefix
    stripped, sorted by start, outer before inner."""
    return [xplane.Span(s.name[len(PREFIX):], s.start, s.end, s.args)
            for s in trace.program_spans if s.name.startswith(PREFIX)]


def load(path: str) -> list[xplane.Span]:
    """The engine's spans in the profile at ``path``."""
    return engine_spans(xplane.load(path))


def _path(spans: list[xplane.Span], t: float) -> str:
    over = [s.name for s in spans if s.start <= t < s.end]   # outer first
    return "/".join(over) if over else OUTSIDE


def _idle(ops: list[xplane.Op], lo: float, hi: float) -> list[tuple[float, float]]:
    iv = xplane.busy(ops, lo, hi)
    edges = [lo] + [x for ab in iv for x in ab] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def _cut(a: float, b: float, spans: list[xplane.Span]) -> dict[str, float]:
    """Nanoseconds of [a, b) by phase path, cut at the span edges in it."""
    near = [s for s in spans if s.start < b and s.end > a]
    edges = sorted({a, b} | {x for s in near for x in (s.start, s.end) if a < x < b})
    out: dict[str, float] = {}
    for x, y in zip(edges, edges[1:]):
        p = _path(near, (x + y) / 2)
        out[p] = out.get(p, 0.0) + (y - x)
    return out


def check_nesting(spans: list[xplane.Span]) -> None:
    """Engine spans come from one thread's nested phases: two that overlap
    without one holding the other make "innermost" meaningless."""
    open_: list[xplane.Span] = []
    for s in spans:
        while open_ and open_[-1].end <= s.start:
            open_.pop()
        if open_ and s.end > open_[-1].end:
            raise ValueError(f"engine spans {open_[-1].name!r} and {s.name!r} "
                             f"overlap without nesting")
        open_.append(s)


def idle_by_phase(trace: xplane.Trace, spans: list[xplane.Span]) -> dict[str, float]:
    """Seconds the chips sat idle in the traced window (``xplane.window``),
    by phase path, averaged over the chips as ``xplane.reduce`` averages
    busy time.  Raises ValueError where an op ends before it starts, the
    spans do not nest, or the parts do not add up to the window less its
    busy time."""
    lo, hi = xplane.window(trace)
    if not trace.devices:
        raise ValueError("the trace holds no TPU device plane")
    bad = [o for ops in trace.devices for o in ops if not o.end >= o.start]
    if bad:
        raise ValueError(f"{len(bad)} device ops end before they start: {bad[0]}")
    check_nesting(spans)
    parts: dict[str, float] = {}
    busy_ns = 0.0
    for ops in trace.devices:
        busy_ns += sum(b - a for a, b in xplane.busy(ops, lo, hi))
        for a, b in _idle(ops, lo, hi):
            for p, ns in _cut(a, b, spans).items():
                parts[p] = parts.get(p, 0.0) + ns
    n = len(trace.devices)
    idle_ns = (hi - lo) * n - busy_ns
    if not abs(sum(parts.values()) - idle_ns) <= 1e-6 * max(1.0, hi - lo) * n:
        raise ValueError(f"idle parts sum to {sum(parts.values()) / n / 1e9!r} s, "
                         f"the window is idle {idle_ns / n / 1e9!r} s")
    return {p: ns / n / 1e9 for p, ns in sorted(parts.items(), key=lambda kv: -kv[1])}


def under(parts: dict[str, float], phase: str) -> float:
    """Seconds of the parts whose path passes through ``phase``."""
    return sum(s for p, s in parts.items() if phase in p.split("/"))


def longest_gaps(trace: xplane.Trace, spans: list[xplane.Span],
                 top: int = 10) -> list[tuple[float, dict[str, float]]]:
    """The ``top`` longest idle gaps of any chip in the traced window: each
    its seconds and its seconds by phase path, longest part first."""
    lo, hi = xplane.window(trace)
    gaps = [g for ops in trace.devices for g in _idle(ops, lo, hi)]
    gaps.sort(key=lambda g: g[0] - g[1])
    return [((b - a) / 1e9,
             {p: ns / 1e9 for p, ns in sorted(_cut(a, b, spans).items(),
                                              key=lambda kv: -kv[1])})
            for a, b in gaps[:top]]
