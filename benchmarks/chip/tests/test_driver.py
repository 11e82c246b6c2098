"""What the driver records of each engine step: the program's counters."""
import contextlib
import dataclasses

import pytest

import run as harness


@dataclasses.dataclass
class Stats:
    generated_tokens: int = 0
    decode_time: float = 0.0
    experts_touched: int = 0          # a counter the harness has no line for
    health: str = "healthy"
    shed: bool = False
    ttfts: list = dataclasses.field(default_factory=list)


class Engine:
    def __init__(self):
        self.stats = Stats()

    def step(self):
        self.stats.decode_time += 0.25
        self.stats.experts_touched += 7
        self.stats.health = "spilling"
        self.stats.shed = True
        self.stats.ttfts.append(1.0)


def test_every_numeric_stats_field_is_a_step_counter():
    drv = harness.Driver(Engine(), None, 0, 16, lambda *a, **k: contextlib.nullcontext())
    first, second = drv.step(), drv.step()
    want = {"generated_tokens": 0, "decode_time": 0.25, "experts_touched": 7}
    assert first.counters == want
    assert second.counters == pytest.approx(want)
    assert (first.i, second.i) == (0, 1)
    assert first.decode_s == first.counters["decode_time"]
    assert harness.counters(Stats(3, 1.5, 2)) == {
        "generated_tokens": 3, "decode_time": 1.5, "experts_touched": 2}


def test_tokens_the_engine_counts_must_match_the_requests():
    eng = Engine()
    eng.step = lambda: setattr(eng.stats, "generated_tokens", 1)
    drv = harness.Driver(eng, None, 0, 16, lambda *a, **k: contextlib.nullcontext())
    with pytest.raises(RuntimeError, match="engine counted 1 tokens"):
        drv.step()
