"""What one run records, and what every metric reader reads.

Times are host wall-clock seconds (``time.time()``, the clock the engine
stamps requests with).  A reader (``metrics/<name>.py``) defines
``read(run: Run) -> float | None`` and returns None where the run holds
nothing for it to read.  What a reader may read: the steps and requests of
the window, each step's program counters (``Step.counters``), the trace's
harness and program spans (``xplane.Trace``), and the architecture module
(``Run.arch``) that counts the work of a step.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Step:
    i: int
    start: float
    end: float
    decode_s: float         # the engine's own decode timer over this step
    decode_tokens: int      # requests that got a decode token (active slots)
    first_tokens: int       # requests admitted, i.e. given a first token
    ctx: int                # cached tokens the decode attended over, in all
    # the change over the step of every int and float field of the
    # engine's stats (``EngineStats``), by field name
    counters: dict = dataclasses.field(default_factory=dict)

    @property
    def kind(self) -> str:
        if self.first_tokens:
            return "admitting"
        return "decode-only" if self.decode_tokens else "idle"


@dataclasses.dataclass
class Req:
    rid: int
    prompt_len: int
    max_new_tokens: int
    due: float              # when the request was due
    submit: float = 0.0
    admit: float = 0.0
    first: float = 0.0
    times: list = dataclasses.field(default_factory=list)   # per token
    done: float = 0.0


@dataclasses.dataclass
class Run:
    model: dict                     # config file's "model" block
    arch: object                    # its architecture module (references/)
    mix: dict                       # traffic file
    peak: dict                      # peaks.json row of this chip
    seconds: float                  # --seconds
    t0: float                       # window opened
    t1: float                       # window closed (end of its last step)
    steps: list                     # Step, in the window
    requests: list                  # Req, every request submitted
    setup_s: float
    memory_peak_bytes: int | None
    admission_s: float | None = None      # engine "admission" spans, window
    trace: object = None                  # xplane.Trace of the traced part
    trace_steps: list = dataclasses.field(default_factory=list)
    reduced: dict | None = None           # xplane.reduce() of it

    @property
    def slots(self) -> int:
        return int(self.mix["slots"])

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def due_in_window(self) -> list:
        return [r for r in self.requests if self.t0 <= r.due < self.t0 + self.seconds]

    def admitted_in_window(self) -> list:
        return [r for r in self.requests if r.first and self.t0 <= r.first <= self.t1]


def percentile(values, q: float) -> float | None:
    return float(np.percentile(np.asarray(values, float), q)) if len(values) else None
