"""A new architecture reaches the harness as files alone.

The fixture ``toy-moe`` (``fixtures/configs/toy-moe.json``,
``fixtures/references/toy_moe.py``) has two stacks (one dense layer, two
mixture-of-experts layers), rank-3 expert leaves, a router, a K-only
latent KV and a decode step whose bytes read a program counter.  The
harness finds its module by the config's ``reference`` and drives it
through the weights, the layout comparison, the program-key check and
both work readers, with no line of its own for it."""
import json
from pathlib import Path

import jax
import pytest

import manifest
import record
import system
import weights
import work
import xplane

BENCH = Path(manifest.__file__).resolve().parent
ROOT = BENCH.parents[1]
FIX = Path(__file__).parent / "fixtures"
PEAK = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
SEED = 2**33 + 5


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    bench = tmp_path_factory.mktemp("bench")
    (bench / "metrics").symlink_to(BENCH / "metrics")
    (bench / "references").symlink_to(FIX / "references")
    config = json.loads((FIX / "configs" / "toy-moe.json").read_text())
    man = manifest.Manifest({}, ROOT, bench)
    return man, config, config["model"], man.reference(config)


def test_layout_has_both_stacks_and_rank3_experts(toy):
    _, _, m, arch = toy
    lay = weights.layout(arch, m)
    assert set(lay) == {"dense", "moe", "embed", "final_w", "lm_head"}
    assert lay["dense"]["wi"] == (1, 64, 256)
    assert lay["moe"]["experts_wi"] == (2, 4, 64, 64)
    assert lay["moe"]["experts_wdown"] == (2, 4, 32, 64)
    assert lay["moe"]["router"] == (2, 64, 4)
    assert "router" not in lay["dense"]


def test_weights_fit_the_layout_and_each_stack_has_its_own_keys(toy):
    _, _, m, arch = toy
    params = weights.make_params(arch, m, SEED)
    system.compare_layout(params, weights.layout(arch, m))
    assert params["moe"]["experts_wi"].dtype == jax.numpy.bfloat16
    # the same leaf name in two stacks is drawn apart
    assert not bool((params["dense"]["wq"][0] == params["moe"]["wq"][0]).all())
    assert not bool((params["moe"]["wq"][0] == params["moe"]["wq"][1]).all())


def test_the_layout_comparison_names_a_misfit(toy):
    _, _, m, arch = toy
    program = jax.eval_shape(lambda: weights.make_params(arch, m, SEED))
    system.compare_layout(program, weights.layout(arch, m))
    program["moe"]["experts_wi"] = jax.ShapeDtypeStruct((2, 4, 64, 32), jax.numpy.bfloat16)
    with pytest.raises(ValueError, match="experts_wi"):
        system.compare_layout(program, weights.layout(arch, m))
    del program["moe"]["experts_wi"]
    with pytest.raises(ValueError, match="experts_wi"):
        system.compare_layout(program, weights.layout(arch, m))


def test_program_keys_are_the_architectures(toy):
    _, config, _, arch = toy
    cfg = system.model_config(ROOT, config, arch)
    assert (cfg.kv_lora_rank, cfg.n_experts, cfg.top_k) == (32, 4, 2)
    bad = dict(config, model=dict(config["model"], experts_per_token=6))
    with pytest.raises(ValueError, match="experts_per_token"):
        system.model_config(ROOT, bad, arch)


def test_latent_kv_is_one_k_only_head():
    arch = manifest.load_module(FIX / "references" / "toy_moe.py")
    m = json.loads((FIX / "configs" / "toy-moe.json").read_text())["model"]
    assert arch.kv_bytes_per_token(m) == 3 * (32 + 8) * 2


def _run(m, arch, touched):
    """Two decode steps of 4 requests; each step's counter says how many
    experts its batch touched, summed over the MoE layers."""
    steps = [record.Step(i, 0.1 * i, 0.1 * i + 0.1, 0.05, 4, 0, 4 * 100,
                         {arch.COUNTER: touched, "decode_time": 0.05})
             for i in range(2)]
    ops = [xplane.Op("splitk_gemm", s.start * 1e9 + 1e6, s.start * 1e9 + 2e6)
           for s in steps]
    spans = [xplane.Span("step", s.start * 1e9, s.end * 1e9, {"i": s.i}) for s in steps]
    return record.Run(model=m, arch=arch, mix={"kind": "offline", "slots": 4}, peak=PEAK,
                      seconds=0.2, t0=0.0, t1=0.2, steps=steps, requests=[], setup_s=1.0,
                      memory_peak_bytes=None, trace=xplane.Trace([ops], spans),
                      trace_steps=steps)


def test_both_work_readers_count_the_architecture(toy):
    man, _, m, arch = toy
    mfu = man.reader({"name": "decode_mfu"})
    roof = man.reader({"name": "splitk_gemm_roofline"})
    few, all_ = _run(m, arch, touched=3), _run(m, arch, touched=8)
    # the experts' bytes follow the counter: memory-bound, so the share does too
    b = 2 * arch.expert_weights(m) / PEAK["hbm_bytes_per_s"]
    assert 100 * 2 * 5 * b / 0.1 == pytest.approx(mfu(all_) - mfu(few), rel=1e-9)
    # splitk_gemm calls are the same whatever the routing: no router, no experts
    want = sum(g.count * work.least_time(*work.gemm_call(m, g, 4), PEAK)[0]
               for g in arch.kernel_gemms(m))
    assert roof(few) == roof(all_) == pytest.approx(100 * 2 * want / 2e-3, rel=1e-12)
    assert {g.name for g in arch.kernel_gemms(m)} == {
        "wq", "wkv_a", "wo", "wi", "wdown", "lm_head"}


def test_a_step_without_the_counter_is_an_error(toy):
    _, _, m, arch = toy
    with pytest.raises(KeyError, match=arch.COUNTER):
        arch.decode_step(m, record.Step(0, 0.0, 0.1, 0.05, 4, 0, 400))
