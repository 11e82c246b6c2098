"""Compile meter: what JAX built, and in which engine phase.

JAX reports the duration of each step of building a program through its
monitoring hooks.  :class:`CompileMeter` listens to four of them and books
each duration to the engine phase open at the time (the recorder's
``phase()`` stack as a path such as ``step/admission/prefill``;
``outside`` when no phase is open):

* ``jaxpr_trace`` — tracing a Python function to a jaxpr;
* ``lower`` — lowering the jaxpr to an MLIR module;
* ``backend_compile`` — the XLA build, or the load from the persistent
  compilation cache where that hits;
* ``cache_load`` — the persistent-cache read on a hit.

A jitted function traced inside another is a trace event inside the
outer one, and ``cache_load`` lies inside ``backend_compile``.  So each
event is booked with its *exclusive* seconds (its duration less the
events that ended inside it, known from the start JAX announces for the
first three), and the booked seconds add up to wall time spent building.

The meter belongs to an enabled `ChromeTraceRecorder`, which writes one
``compile`` span per event.  The JAX listeners hold the meter only
weakly, so a recorder nobody closes is still freed; ``close()``
unregisters them.
"""
from __future__ import annotations

import collections
import threading
import weakref
from typing import Callable

from jax import monitoring

EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jaxpr_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
OUTSIDE = "outside"


class CompileMeter:
    """Exclusive seconds and counts of JAX's build steps, by (phase path,
    event).

    ``phase_path()`` names the phase open now; ``on_event(path, event,
    seconds, exclusive, fun)`` is called for every booked event (the
    recorder writes its span there)."""

    def __init__(self, phase_path: Callable[[], str],
                 on_event: Callable[[str, str, float, float, str], None]):
        self.seconds_by: dict[tuple[str, str], float] = collections.defaultdict(float)
        self.counts: dict[tuple[str, str], int] = collections.defaultdict(int)
        self._phase_path = phase_path
        self._on_event = on_event
        self._local = threading.local()      # per thread: [event, child s] open
        ref = weakref.ref(self)

        def started(event: str, _value: float, **_kw) -> None:
            meter = ref()
            if meter is not None and event in EVENTS:
                meter._open().append([EVENTS[event], 0.0])

        def ended(event: str, duration: float, **kw) -> None:
            meter = ref()
            if meter is not None and event in EVENTS:
                meter._book(EVENTS[event], duration, str(kw.get("fun_name", "")))

        self._listeners: tuple | None = (started, ended)
        monitoring.register_scalar_listener(started)
        monitoring.register_event_duration_secs_listener(ended)

    def _open(self) -> list:
        if not hasattr(self._local, "stack"):
            self._local.stack = []
        return self._local.stack

    def _book(self, event: str, seconds: float, fun: str) -> None:
        stack = self._open()
        child = 0.0
        if stack and stack[-1][0] == event:
            child = stack.pop()[1]
        if stack:
            stack[-1][1] += seconds
        exclusive = max(0.0, seconds - child)
        path = self._phase_path() or OUTSIDE
        self.seconds_by[(path, event)] += exclusive
        self.counts[(path, event)] += 1
        self._on_event(path, event, seconds, exclusive, fun)

    def seconds(self, under: str | None = None) -> float:
        """Seconds spent building, booked to phase paths that pass through
        the phase ``under`` (every path when None)."""
        return sum(s for (path, _), s in self.seconds_by.items()
                   if under is None or under in path.split("/"))

    def close(self) -> None:
        """Unregister the JAX listeners; later events are not booked."""
        if self._listeners is not None:
            started, ended = self._listeners
            monitoring.unregister_scalar_listener(started)
            monitoring.unregister_event_duration_listener(ended)
            self._listeners = None
