"""Weights from the seed, laid out by the architecture module: the same
bits as before the layout moved into the module, leaf by leaf."""
import hashlib
import json
from pathlib import Path

import jax
import numpy as np
import pytest

import manifest
import weights

BENCH = Path(weights.__file__).resolve().parent
FIX = Path(__file__).parent / "fixtures"
SEED = 2**33 + 11

# sha256 of each leaf's bytes (first 16 hex digits), from make_params at
# SEED with the dense layout as weights.py held it before references/
# took it over
SUMS = {
    "tiny-coder": {
        "embed": "95cfbb020172652f", "final_b": "f121275b46a41e9e",
        "final_w": "02558a9d068371e0", "layers/bdown": "a76ce5ff7e49c837",
        "layers/bi": "1b22db218241fc51", "layers/bkv": "1ba968282790a8f1",
        "layers/bq": "14be283adc817ab7", "layers/ln1_b": "2967705b66fc7ce9",
        "layers/ln1_w": "bb96f9d7ed43529b", "layers/ln2_b": "e555ea80b49bb2dd",
        "layers/ln2_w": "920d3472f7f3f646", "layers/wdown": "ed2f7d9422597b70",
        "layers/wi": "725916387129302a", "layers/wkv": "cfd64819d7b1fa27",
        "layers/wo": "0ef2bf0f384d7999", "layers/wq": "6f9602dfae1c456d",
        "lm_head": "4c3e63a04cedd888"},
    "tiny-chat": {
        "embed": "3dcb050ce4602f7c", "final_w": "625397affa12309e",
        "layers/bkv": "0cee4a996a5fefb3", "layers/bq": "66b13265bddad350",
        "layers/ln1_w": "fc5ee163037fe4da", "layers/ln2_w": "1370cb7ad7179194",
        "layers/wdown": "f850e7d50947a61c", "layers/wi": "3d348a405d008357",
        "layers/wkv": "13fc75cb76250e66", "layers/wo": "947de60185c60627",
        "layers/wq": "b7e9d0b80bcf2436", "lm_head": "0a30557dec76497e"},
}


def config(name):
    """A fixture config's model block and architecture module, the
    fixture's own where it has one."""
    c = json.loads((FIX / "configs" / f"{name}.json").read_text())
    path = FIX / "references" / f"{c['reference']}.py"
    if not path.exists():
        path = BENCH / "references" / f"{c['reference']}.py"
    return c["model"], manifest.load_module(path)


def flat(tree):
    return {"/".join(str(getattr(k, "key", k)) for k in p): v
            for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def digest(a) -> str:
    return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(SUMS))
def test_dense_leaves_are_the_bits_they_were(name):
    m, arch = config(name)
    params = weights.make_params(arch, m, SEED)
    assert {k: digest(v) for k, v in flat(params).items()} == SUMS[name]


@pytest.mark.parametrize("name", ["tiny-coder", "tiny-chat", "toy-moe"])
def test_a_layer_made_alone_is_that_layer_of_the_tree(name):
    """The reference makes its weights a layer at a time; each must equal
    the served tree's, in every stack."""
    m, arch = config(name)
    params = weights.make_params(arch, m, SEED)
    key = weights.base_key(SEED)
    # jitted as check.py jits them: eager arithmetic may round differently
    make_layer = jax.jit(lambda stack, i: weights.make_layer(arch, m, key, stack, i),
                         static_argnums=0)
    make_top = jax.jit(lambda name: weights.make_top(arch, m, key, name), static_argnums=0)
    for stack, (n, _) in arch.stacks(m).items():
        for i in (0, n - 1):
            for leaf, a in make_layer(stack, i).items():
                np.testing.assert_array_equal(np.asarray(a), np.asarray(params[stack][leaf][i]))
    for leaf in arch.top_shapes(m):
        np.testing.assert_array_equal(np.asarray(make_top(leaf)), np.asarray(params[leaf]))
