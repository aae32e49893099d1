"""Every model's parameter tree, through what `models/stack.py` shares (`pattern_blocks`, `per_leaf`, `draw`,
`draw_layer`) or through its own: for each nano configuration of `benchmark/configs/`, `param_logical_axes`,
`frozen_params` where the model defines it and `jax.eval_shape(init_params)` have one tree structure, and
`init_params` at `PRNGKey(0)` is, to the bit, what the tree before PR 63 drew (`tools/lowered_fingerprint.py
--params` run on commit 54ac046: its `seed0`). A model's key scheme is its own and part of a cell's result: another
draw is another routing and another held load a layer. Parameters are built, no step is compiled."""

import os
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "tools")]

import lowered_fingerprint  # noqa: E402
from ray_tpu.models.training import model_for  # noqa: E402

DRAWN_BEFORE = {
    "glm4-moe-lite-nano": "be37fc95a48467af", "gpt2-nano": "c13d04362593ac00", "keye-vl2-nano": "92460aa3ce5b6e3c",
    "lfm2-nano": "1d61c5948566f75b", "olmo-hybrid-nano": "7f11c542d0ce6481", "olmoe-nano": "5d790fe0d7854f89",
    "sdar-nano": "5806c8772689c752", "solar-open2-nano": "d31cc99197298c4c", "trinity-nano": "dc19f5da3c133fe5",
    "xing4-nano": "02b3e5cc84f92fe8",  # PR 66: GLM's tree and key scheme with two `hc_*` groups a layer
    "smallthinker-nano": "7c2de87a54adfe30",  # PR 70: `stack.lm_tree` with no leading layer, `gqa_experts`' leaves less the head norms
    "granite-hybrid-nano": "e1fa16fbabd769f7",  # PR 73: `stack.lm_tree` with no head of its own (the table is tied)
}


def test_every_nano_configuration_is_held():
    nanos = {f[:-len(".json")] for f in os.listdir(os.path.join(REPO, "benchmark", "configs")) if "nano" in f}
    assert nanos == set(DRAWN_BEFORE)  # a new family: its digest from `tools/lowered_fingerprint.py --params`


@pytest.mark.parametrize("name", sorted(DRAWN_BEFORE))
def test_one_tree_structure_and_the_first_parameters_to_the_bit(name, monkeypatch):
    monkeypatch.chdir(REPO)
    _, cfg = lowered_fingerprint.configuration(name)
    model = model_for(cfg)
    shapes = jax.eval_shape(lambda: model.init_params(cfg, jax.random.PRNGKey(0)))
    structure = jax.tree.structure(shapes)
    assert jax.tree.structure(model.param_logical_axes(cfg), is_leaf=lambda x: isinstance(x, tuple)) == structure
    if hasattr(model, "frozen_params"):
        frozen = model.frozen_params(cfg)
        assert jax.tree.structure(frozen) == structure and all(isinstance(x, bool) for x in jax.tree.leaves(frozen))
    params = jax.jit(lambda key: model.init_params(cfg, key))(jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), params) == jax.tree.map(lambda s: (s.shape, s.dtype), shapes)
    assert lowered_fingerprint.tree_digest(params) == DRAWN_BEFORE[name]
