"""The generator repeats per seed and offers every seed the same work."""
import collections
import json
from pathlib import Path

import numpy as np

import traffic

BENCH = Path(traffic.__file__).resolve().parent
FIX = Path(__file__).parent / "fixtures"
MIXES = {p.stem: json.loads(p.read_text())
         for d in (BENCH / "traffic", FIX / "traffic") for p in d.glob("*.json")}


def test_same_seed_same_requests_and_prompts():
    for mix in MIXES.values():
        assert traffic.generate(mix, 7) == traffic.generate(mix, 7)
    a = traffic.prompt_tokens(2**33 + 5, 3, 64, 1000)
    assert np.array_equal(a, traffic.prompt_tokens(2**33 + 5, 3, 64, 1000))
    assert not np.array_equal(a, traffic.prompt_tokens(5, 3, 64, 1000))
    assert a.min() >= 3 and a.max() < 1000


def test_every_seed_gets_the_same_work_in_another_order():
    for mix in MIXES.values():
        n = mix["block"]
        a, b = traffic.generate(mix, 1), traffic.generate(mix, 2**32 + 9)
        assert [(s.prompt_len, s.max_new_tokens) for s in a] != \
               [(s.prompt_len, s.max_new_tokens) for s in b]
        for k in range(0, len(a) - n + 1, n):
            ca = collections.Counter((s.prompt_len, s.max_new_tokens) for s in a[k:k + n])
            cb = collections.Counter((s.prompt_len, s.max_new_tokens) for s in b[k:k + n])
            assert ca == cb


def test_lengths_stay_in_the_mix():
    for mix in MIXES.values():
        specs = traffic.generate(mix, 3)
        assert {s.prompt_len for s in specs} <= set(mix["prompt"]["buckets"])
        assert all(mix["output"]["min"] <= s.max_new_tokens <= mix["output"]["max"]
                   for s in specs)
        assert traffic.engine_max_len(mix) >= max(mix["prompt"]["buckets"])


def test_poisson_arrivals_keep_the_rate():
    mix = MIXES["tiny-poisson"]
    specs = traffic.generate(mix, 11)
    due = np.array([s.due_s for s in specs])
    assert due[0] == 0.0 and np.all(np.diff(due) > 0)
    rate = (len(due) - 1) / due[-1]
    assert abs(rate / mix["rate_rps"] - 1) < 0.1
    n = mix["block"]
    other = np.array([s.due_s for s in traffic.generate(mix, 12)])
    # the second block's n gaps are the template's, in each seed's order
    assert np.allclose(sorted(np.diff(due[n - 1:2 * n])), sorted(np.diff(other[n - 1:2 * n])))


def test_offline_is_due_at_once():
    specs = traffic.generate(MIXES["sharegpt-offline"], 4)
    assert {s.due_s for s in specs} == {0.0}
    assert len(specs) == MIXES["sharegpt-offline"]["requests"]


def test_sharegpt_mix_keeps_the_source_means():
    """The vLLM paper's ShareGPT means: 161.31 tokens in, 337.99 out."""
    mix = MIXES["sharegpt-offline"]
    block = traffic.generate(mix, 2**33 + 3)[:mix["block"]]
    assert abs(np.mean([s.prompt_len for s in block]) / 161.31 - 1) < 0.03
    assert abs(np.mean([s.max_new_tokens for s in block]) / 337.99 - 1) < 0.03


def test_bucket_rounding():
    assert traffic.to_bucket(1, [128, 256]) == 128
    assert traffic.to_bucket(129, [128, 256]) == 256
    assert traffic.to_bucket(999, [128, 256]) == 256
