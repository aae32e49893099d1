"""`ray_tpu/models/smallthinker.py` (PR 70): the model against the plain reference of `benchmark/models/smallthinker.py`
on seeded weights, loss, logits' loss and every gradient leaf, at groups of 7 query heads on one key/value head, on a row
longer than the nano's window so that the two layer kinds differ; the router's tap (the layer's normed input, before
attention) and the activation (ReLU, not SiLU) told from their neighbours; bf16 where float32 is stated told by the same
limits; the four expert shares' partial sums adding up to the uncut layer; the step through `make_train_step`."""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.models import smallthinker as bench
from ray_tpu.models import create_train_state, default_optimizer, make_train_step, smallthinker
from ray_tpu.models.moe import experts_of, route_and_sort
from ray_tpu.models.training import model_for

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 64  # four windows of the nano's 16
# The program in float32 against the reference in float32 computes the same function in another order of sums: the
# loss reads 1e-6 apart and every leaf 5e-7 of its largest entry (PR 70, this machine). The limits stand 20 times over
# that, and 25 times under what bf16 activations give (loss 5e-5, leaves 6e-3..1.2e-1 of their norm).
LOSS_ATOL, LEAF_RTOL = 2e-5, 2e-4


@pytest.fixture(scope="module")
def nano():
    with open(os.path.join(REPO, "benchmark", "configs", "smallthinker-nano.json")) as fh:
        c = {**json.load(fh), "dtype": "float32"}  # the program in the reference's own precision: what differs is the function
    cfg = bench.smallthinker_config(c)
    params = smallthinker.init_params(cfg, jax.random.PRNGKey(7))
    leaves, tree = jax.tree.flatten(params)  # norms off 1, so that a scale left out shows
    keys = jax.random.split(jax.random.PRNGKey(8), len(leaves))
    params = jax.tree.unflatten(tree, [x + 0.05 * jax.random.normal(k, x.shape) if x.shape[-1] == 64 and x.ndim <= 2
                                       and x.shape[0] != 256 else x for k, x in zip(keys, leaves)])
    tokens = jax.random.randint(jax.random.PRNGKey(9), (2, SEQ + 1), 0, c["vocab_size"])
    return c, cfg, params, tokens


def _program(cfg, tokens):
    return jax.jit(jax.value_and_grad(lambda p: smallthinker.loss_fn(p, {"tokens": tokens}, cfg)))


def _reference(c, tokens, **faults):
    return jax.jit(jax.value_and_grad(lambda p: bench.reference_loss(p, tokens, c, **faults), has_aux=True))


@pytest.fixture(scope="module")
def both(nano):
    c, cfg, params, tokens = nano
    return _program(cfg, tokens)(params), _reference(c, tokens)(params)


def _paths(tree):
    return {jax.tree_util.keystr(path): leaf for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def _worst_leaf(grads, ref_grads):
    """The largest `|g - ref| / max|ref|` over the leaves, and its path."""
    mine, theirs = _paths(grads), _paths(ref_grads)
    assert mine.keys() == theirs.keys()
    return max((float(jnp.abs(g - theirs[path]).max() / jnp.abs(theirs[path]).max()), path) for path, g in mine.items())


def test_the_program_is_found_by_its_configuration_and_not_imported_by_the_package(nano):
    import ray_tpu.models as package

    c, cfg, params, _ = nano
    assert model_for(cfg) is smallthinker and not hasattr(smallthinker, "update_buffers")
    assert "smallthinker" not in open(package.__file__).read()
    assert cfg.kinds == ("full", "window", "window", "window") == cfg.period == tuple(bench.layer_kinds(c))
    assert (cfg.n_head, cfg.n_kv_head, cfg.held, cfg.first_expert_held, cfg.n_experts) == (7, 1, 4, 4, 16)  # a group of 7
    assert sum(x.size for x in jax.tree.leaves(params)) == smallthinker.num_params(cfg) == bench.num_params(c)
    axes = smallthinker.param_logical_axes(cfg)
    assert jax.tree.structure(jax.tree.map(lambda _: 0, params)) == jax.tree.structure(
        jax.tree.map(lambda _: 0, axes, is_leaf=lambda x: isinstance(x, tuple)))
    assert not [path for path in _paths(params) if "q_norm" in path or "k_norm" in path or "bias" in path]


def test_the_published_stack_is_thirteen_periods_with_nothing_in_front():
    cfg = smallthinker.SmallThinkerConfig()
    assert cfg.kinds[:8] == ("full", "window", "window", "window") * 2 and len(cfg.kinds) == 52
    stack = smallthinker.pattern(cfg)
    assert (stack.leading, stack.period, stack.n_periods, stack.trailing) == ((), ("full", "window", "window", "window"), 13, ())
    assert smallthinker.num_params(cfg) == 21_506_562_560  # 21.5 B: 52 x 398,627,840 + 2 x 151,936 x 2,560 + 2,560
    assert smallthinker.kept_pairs(16384, 4096) / smallthinker.kept_pairs(16384, None) == pytest.approx(0.4375, abs=1e-4)
    with pytest.raises(AssertionError, match="two kinds written"):
        smallthinker.SmallThinkerConfig(rope_layout=(1, 1, 1, 1))


def test_the_loss_and_the_choices_are_the_references(nano, both):
    _, cfg, params, tokens = nano
    (loss, _), ((ref_loss, ref), _) = both
    assert float(loss) == pytest.approx(float(ref_loss), abs=LOSS_ATOL)
    stats = jax.jit(lambda p: smallthinker.routing_stats(p, tokens[:, :-1], cfg))(params)
    chose = jnp.take_along_axis(ref["chosen"], stats["experts"], axis=-1)
    assert bool(chose.all()) and np.array_equal(np.asarray(stats["tokens_per_expert"]), np.asarray(ref["chosen"].sum(axis=1)))
    assert np.array_equal(np.asarray(stats["held_pairs"]), np.asarray(ref["held_pairs"])) and int(stats["dropped"].sum()) == 0
    live = np.asarray(ref["relu_live"]) / (np.asarray(ref["held_pairs"]) * cfg.d_expert)
    np.testing.assert_allclose(np.asarray(stats["relu_live_share"]), live, atol=2e-3)  # a gate within rounding of zero
    assert 0.3 < live.min() and live.max() < 0.7  # ReLU leaves about half of the units live at seeded weights


def test_every_gradient_leaf_is_the_references(both):
    (_, grads), (_, ref_grads) = both
    assert len(_paths(grads)) == 3 + 4 * 10
    worst, path = _worst_leaf(grads, ref_grads)
    assert worst < LEAF_RTOL, (path, worst)


def test_bf16_where_float32_is_stated_fails_the_same_limits(nano, both):
    """The comparison is tight enough to tell a precision: the program with bf16 activations and operands (what the
    cell runs, but the nano's file states float32 here) is outside the loss's limit or a leaf's."""
    _, cfg, params, tokens = nano
    _, (_, ref_grads) = both
    loss, grads = _program(dataclasses.replace(cfg, dtype=jnp.bfloat16), tokens)(params)
    (_, ((ref_loss, _), _)) = both
    assert abs(float(loss) - float(ref_loss)) > LOSS_ATOL or _worst_leaf(grads, ref_grads)[0] > LEAF_RTOL
    assert _worst_leaf(grads, ref_grads)[0] > 10 * LEAF_RTOL


@pytest.mark.parametrize("fault", [{"router_reads": "post_attention"}, {"act": "silu"}, {"window": 15}, {"window": 17},
                                   {"rope_in_full": True}])
def test_another_tap_activation_window_or_rotation_is_another_function(nano, both, fault):
    """Each planted in the reference: the router reading the post-attention state, SwiGLU, a window off by one key
    either way, a rotation in the full layer. The program against the true reference is under
    `LEAF_RTOL` at every leaf; against each of these it is a hundred times outside at some leaf. (The loss tells none of
    them at seeded weights, where the experts and the attention add little to a token's logits: 1e-6..7e-6 apart.)"""
    c, _, params, tokens = nano
    (_, grads), _ = both
    _, faulty = _reference(c, tokens, **fault)(params)
    worst, path = _worst_leaf(grads, faulty)
    assert worst > 100 * LEAF_RTOL, (fault, path, worst)


def test_the_routers_gradient_comes_through_the_layers_input_and_not_through_attention(nano):
    """With the attention's output projection zeroed (h = x: nothing of attention reaches the experts' input), a
    router that read the post-attention state would still get a gradient, through m; so would one that reads n. What
    tells them: with W_q, W_k, W_v of that layer perturbed the router's logits do not move (n does not depend on
    them), and the router's gradient with attention's output zeroed is non-zero and equals the reference's."""
    c, cfg, params, tokens = nano
    zeroed = jax.tree.map(lambda x: x, params)
    zeroed["blocks"]["period"][1] = {**params["blocks"]["period"][1], "wo": jnp.zeros_like(params["blocks"]["period"][1]["wo"])}
    _, grads = _program(cfg, tokens)(zeroed)
    (_, _), ref_grads = _reference(c, tokens)(zeroed)
    g, ref = grads["blocks"]["period"][1]["moe"]["router_w"], ref_grads["blocks"]["period"][1]["moe"]["router_w"]
    assert float(jnp.abs(g).max()) > 1e-6
    np.testing.assert_allclose(np.asarray(g), np.asarray(ref), atol=LEAF_RTOL * float(jnp.abs(ref).max()))
    # The first half of the layer reads what `qkv_part` is handed and nothing of attention: its choices are those of a
    # router on N_in(x), whatever W_q is.
    walked = smallthinker.pattern(cfg, stats=True)
    layer = jax.tree.map(lambda a: a[0], params["blocks"]["period"][1])
    x = jax.random.normal(jax.random.PRNGKey(1), (1, SEQ, cfg.d_model), jnp.float32)
    streams = smallthinker._streams(SEQ, cfg)
    *_, (_, aux) = walked.kinds["window"][0](x, layer, *streams)
    *_, (_, other) = walked.kinds["window"][0](x, {**layer, "wq": 2 * layer["wq"], "wk": -layer["wk"]}, *streams)
    assert np.array_equal(np.asarray(aux["experts"]), np.asarray(other["experts"]))


def test_a_full_layer_takes_no_rotation_and_a_window_layer_does(nano):
    _, cfg, params, _ = nano
    kinds = smallthinker.pattern(cfg).kinds
    x = jax.random.normal(jax.random.PRNGKey(1), (1, SEQ, cfg.d_model), jnp.float32)
    layer = jax.tree.map(lambda a: a[0], params["blocks"]["period"][0])
    cos, sin = smallthinker._streams(SEQ, cfg)
    other = (jnp.roll(cos, 5, axis=0), jnp.roll(sin, 5, axis=0))
    for kind, rotates in (("window", True), ("full", False)):
        q, k, v, _ = kinds[kind][0](x, layer, cos, sin)
        q2, k2, v2, _ = kinds[kind][0](x, layer, *other)
        assert q.shape == (1, 7, SEQ, 16) and k.shape == v.shape == (1, 1, SEQ, 16)
        assert np.array_equal(np.asarray(v), np.asarray(v2))
        assert (not np.array_equal(np.asarray(q), np.asarray(q2))) == rotates and (
            not np.array_equal(np.asarray(k), np.asarray(k2))) == rotates
    # and the two kinds' masks: on a row longer than the window the kinds' attention differs, from the window's end on
    q, k, v, routed = kinds["full"][0](x, layer, cos, sin)
    (full, passed), (window, _) = kinds["full"][2](q, k, v, routed, None, None), kinds["window"][2](q, k, v, routed, None, None)
    assert passed is routed  # what the first part yields beside q, k, v rides through the kind's `attend`
    same = np.isclose(np.asarray(full), np.asarray(window), atol=1e-6).all(axis=(0, 1, 3))
    assert same[:cfg.sliding_window].all() and not same[cfg.sliding_window:].any()


def test_the_four_shares_partial_sums_add_up_to_the_uncut_references_layer(nano):
    """16 of 64 at the published sizes is one of four shares (experts 0-15, 16-31, 32-47, 48-63); at the nano's, four
    shares of four of sixteen. Each share's layer (`route_and_sort` on the router's tensor, `experts_of` on the
    experts') gives a partial sum, and the four add up to what the reference gives for the layer that holds every
    expert; every share's router makes the same choices."""
    c, cfg, _, _ = nano
    whole = dataclasses.replace(cfg, n_experts_held=None, first_expert_held=0)
    moe = jax.tree.map(lambda a: a[0], smallthinker.init_params(whole, jax.random.PRNGKey(3))["blocks"]["period"][0]["moe"])
    moe = {**moe, "w_down": 30 * moe["w_down"]}  # outputs of a size worth comparing
    n, m = (jax.random.normal(jax.random.PRNGKey(k), (2 * SEQ, cfg.d_model), jnp.float32) for k in (5, 6))
    k = cfg.experts_per_token
    uncut, chosen, _, pairs = bench.expert_layer(n, m, moe, k, range(cfg.n_experts), jax.nn.relu)
    assert int(pairs) == 2 * SEQ * k
    total, held = jnp.zeros_like(uncut), 0
    for first in range(0, cfg.n_experts, cfg.held):
        mine = {name: moe[name][first:first + cfg.held] for name in ("w_gate", "w_up", "w_down")}
        routing, aux = route_and_sort(n, moe["router_w"], cfg.held, k=k, norm_topk_prob=True, held_from=first)
        part, report = experts_of(m, routing, mine["w_gate"], mine["w_up"], mine["w_down"], k=k, n_experts=cfg.n_experts,
                                  act=jax.nn.relu)
        assert np.array_equal(np.asarray(aux["tokens_per_expert"]), np.asarray(chosen.sum(axis=0)))
        assert int(report["rows_processed"]) == int(aux["held_pairs"])
        share, _, _, _ = bench.expert_layer(n, m, {**mine, "router_w": moe["router_w"]}, k, range(first, first + cfg.held),
                                            jax.nn.relu)
        np.testing.assert_allclose(np.asarray(part), np.asarray(share), atol=1e-5)  # the share's own reference
        total, held = total + part, held + int(aux["held_pairs"])
    assert held == 2 * SEQ * k
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), atol=2e-5)
    assert float(jnp.abs(uncut).max()) > 1e-2


def test_the_train_step_takes_a_step_and_keeps_a_bf16_copy_of_the_held_experts():
    cfg = smallthinker.SmallThinkerConfig.nano()
    optimizer = default_optimizer(1e-3)
    state = create_train_state(cfg, jax.random.PRNGKey(0), optimizer)
    assert sorted(state.compute) == sorted(f"['blocks']['period'][{place}]['moe']['{name}']"
                                           for place in range(4) for name in ("w_down", "w_gate", "w_up"))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, SEQ + 1), 0, cfg.vocab_size)
    before = jax.tree.map(np.asarray, state.params)
    step = make_train_step(cfg, optimizer, donate=False)
    after, metrics = step(state, {"tokens": tokens})
    assert np.isfinite(float(metrics["loss"])) and float(metrics["grad_norm"]) > 0 and int(metrics["step"]) == 1
    assert 5.0 < float(metrics["loss"]) < 7.0  # ln(256) = 5.55 and half the logits' variance
    moved = _paths(jax.tree.map(lambda a, b: bool(np.any(np.asarray(a) != b)), after.params, before))
    assert all(moved.values())
    text = step.lower(state, {"tokens": tokens}).as_text(debug_info=True)
    for scope in ("qkv/full/moe/router", "qkv/window/moe/dispatch", "attention/window", "attention/full", "window/moe/experts"):
        assert scope in text, scope
