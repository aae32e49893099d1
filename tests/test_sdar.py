"""SDAR's block-diffusion step through `create_train_state` / `make_train_step`
against the plain float32 reference of `benchmark/models/sdar.py`, at nano size
on the CPU (two layers, hidden 64, 4 query heads on 2 key/value heads of 16, rows
of 32 data tokens = 64 positions in blocks of 4, a router over 8 experts of
which this share holds 4, 2 a token); on the chip the same comparison runs at the
published widths.

Beside it: the two-copy pass against the objective's definition, block by
block; the draw against its levels; the shares of the expert layer against the
uncut layer; the step's key; and the negative cases that say what the
comparison can see."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import shared_checks  # noqa: E402
from benchmark.harness.manifest import Manifest  # noqa: E402
from benchmark.models import sdar as bench  # noqa: E402

CONFIG, CELL = "sdar-30b-a3b-chat-ep8", "sdar-30b-a3b-chat-ep8.fed8k"


@pytest.fixture(scope="module")
def nano():
    return Manifest().config("sdar-nano")


@pytest.fixture(scope="module")
def tokens():
    import jax.numpy as jnp

    return jnp.asarray(np.random.default_rng(0).integers(0, 254, (2, 33), dtype=np.int32))


@pytest.fixture(scope="module")
def f32(nano):
    """The nano configuration computed in float32 throughout, and its seeded parameters."""
    import jax

    from ray_tpu.models import sdar as model

    c = {**nano, "dtype": "float32"}
    cfg = bench.model_config(c)
    return c, cfg, jax.jit(lambda key: model.init_params(cfg, key))(jax.random.PRNGKey(7))


@pytest.fixture(scope="module")
def bf16(nano):
    return bench.build(nano, None, 3)


@pytest.fixture(scope="module")
def check():
    return shared_checks.Checked(bench)


# ------------------------------------------------------------------ system against reference
def test_the_bf16_system_is_within_the_written_tolerance_of_the_reference(bf16, check, tokens):
    out = check(bf16, tokens)
    assert out["ok"], out
    assert out["routing"]["dropped"] == 0 and out["state_dtypes_other_than_stated"] == []
    draw = out["block_diffusion"]
    assert 0.2 < draw["masked_share"] < 0.8 and draw["walked_over_live_tiles"] == 1.0
    assert out["routing"]["pairs_per_layer"] == 2 * 2 * 32 * 2  # both copies of both rows, two experts a position


def test_in_float32_they_agree_to_rounding_by_loss_and_leaf(f32, tokens):
    import jax

    from ray_tpu.models import sdar as model

    c, cfg, params = f32
    key = jax.random.PRNGKey(11)
    noised, masked, weight = model.noise(tokens[:, :-1], key, cfg)
    mine, mine_g = jax.jit(jax.value_and_grad(
        lambda p: model.loss_fn(p, {"tokens": tokens}, cfg, step_rng=key)))(params)
    (theirs, _), theirs_g = jax.jit(jax.value_and_grad(
        lambda p: bench.reference_loss(p, tokens[:, :-1], noised, weight, c), has_aux=True))(params)
    assert abs(float(mine) - float(theirs)) < 3e-5
    flat = lambda g: {jax.tree_util.keystr(k): v for k, v in jax.tree_util.tree_leaves_with_path(g)}
    assert flat(mine_g).keys() == flat(theirs_g).keys()
    for name, g in flat(theirs_g).items():
        np.testing.assert_allclose(np.asarray(flat(mine_g)[name]), np.asarray(g), atol=5e-6, rtol=2e-3, err_msg=name)
    assert float(np.abs(np.asarray(flat(theirs_g)["['embed']"])[cfg.mask_token_id]).max()) > 0  # the mask's row learns


@pytest.mark.parametrize("what", ["another_draw", "unweighted", "block_length", "causal"])
def test_what_the_comparison_sees(f32, tokens, what):
    """Another objective under the same name is off in one of the check's three measures by far more than system
    and reference agree to (3e-5, 1e-4 and 1e-3 here). At seeded weights the loss is ln(vocabulary) whatever the mask
    and the gradient's norm is the embedding's and the head's: it is W_q's and W_k's gradients, which reach the loss
    through the masked scores alone, that tell one mask from another."""
    import jax
    import optax

    from ray_tpu.models import sdar as model

    c, cfg, params = f32
    key = jax.random.PRNGKey(11)
    row = tokens[:, :-1]
    noised, masked, weight = model.noise(row, key, cfg)

    def measure(f):
        def of(p):
            loss, grads = jax.value_and_grad(f)(p)
            return (loss, optax.global_norm(grads), optax.global_norm([grads["blocks"]["wq"], grads["blocks"]["wk"]]),
                    grads["blocks"]["wq"])
        return [np.asarray(x) for x in jax.jit(of)(params)]

    mine = measure(lambda p: model.loss_fn(p, {"tokens": tokens}, cfg, step_rng=key))
    same = measure(lambda p: bench.reference_loss(p, row, noised, weight, c)[0])
    if what == "another_draw":  # the reference under its own draw, not the one the system was given
        theirs = lambda p: bench.reference_loss(p, row, *model.noise(row, jax.random.PRNGKey(12), cfg)[::2], c)[0]
    elif what == "unweighted":  # a plain mean over the masked positions
        theirs = lambda p: bench.reference_loss(p, row, noised, masked / masked.sum() * masked.size, c)[0]
    elif what == "block_length":
        theirs = lambda p: bench.reference_loss(p, row, noised, weight, {**c, "block_length": 8})[0]
    else:  # a causal model on the doubled row
        from ray_tpu.ops.flash_attention import xla_attention

        theirs = lambda p: model.causal_lm_loss(_forward_with(
            p, row, noised, cfg, lambda q, k, v: xla_attention(q, k, v, causal=True)), row, weights=weight)
    theirs = measure(theirs)
    off = lambda other: (abs(mine[0] - other[0]), abs(mine[1] - other[1]) / other[1], abs(mine[2] - other[2]) / other[2])
    assert off(same)[0] < 3e-5 and off(same)[1] < 1e-4 and off(same)[2] < 1e-3, off(same)
    by_leaf = lambda other: float(np.abs(mine[3] - other[3]).max() / np.abs(other[3]).max())
    assert by_leaf(same) < 2e-3
    if what == "block_length":  # blocks of 8 for 4 move no norm by a hundredth: the leaves see it, the norms do not
        assert max(off(theirs)) < 1e-2 and by_leaf(theirs) > 3e-2, (off(theirs), by_leaf(theirs))
    else:
        assert off(theirs)[0] > 3e-3 or off(theirs)[1] > 3e-3 or off(theirs)[2] > 3e-2, (what, off(theirs))


def test_parameters_kept_in_bf16_fail_the_check_by_name(bf16, check, tokens):
    import jax.numpy as jnp

    out = check(shared_checks.in_dtype(bf16, jnp.bfloat16), tokens)
    assert out["state_dtypes_other_than_stated"] == ["bfloat16"] and not out["ok"]


# ------------------------------------------------------------------ the two-copy pass is the objective
def _forward_with(params, tokens, noised, cfg, attention):
    """`sdar.forward` with `attention(q, k, v)` where the masked call stands."""
    import jax

    from ray_tpu.models import sdar as model
    from ray_tpu.models.llama import rms_norm
    from ray_tpu.models.stack import block, lm_head

    x, streams = model._two_copies(params, tokens, noised, cfg)
    qkv_part, out_part, _ = model._parts(cfg, tokens.shape[1])
    attend = lambda q, k, v, attention_fn, mesh: (attention(q, k, v),)
    x, _ = jax.lax.scan(lambda x, layer: block(x, layer, cfg, qkv_part, out_part, streams=streams, attend=attend),
                        x, params["blocks"])
    return lm_head(x[:, tokens.shape[1]:], lambda x: rms_norm(x, params["final_norm"], cfg.norm_eps),
                   params["lm_head"], cfg.dtype)


@pytest.mark.parametrize("b", [0, 1, 4, 7])
def test_a_blocks_logits_are_those_of_the_model_run_on_the_clean_blocks_before_it_and_the_noised_block_alone(f32, tokens, b):
    """The definition: block b is predicted from `[x^{<b} ; x~^b]`, the clean
    prefix block-causal among itself, the noised block seeing all of it and
    itself in both directions, at the positions the tokens have in the row."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gqa_experts, sdar as model
    from ray_tpu.models.llama import rms_norm, rope_tables
    from ray_tpu.models.stack import block, lm_head
    from ray_tpu.ops.flash_attention import pack_keep, xla_attention

    c, cfg, params = f32
    size, row = cfg.block_length, tokens[:, :-1]
    noised, _, _ = model.noise(row, jax.random.PRNGKey(5), cfg)
    want = model.forward(params, row, noised, cfg)[:, b * size:(b + 1) * size]
    # The sequence of the definition: (b + 1) blocks, the last one noised.
    ids = jnp.concatenate([row[:, :b * size], noised[:, b * size:(b + 1) * size]], axis=1)
    n = ids.shape[1]
    blk = np.arange(n) // size
    sees = (blk[None, :] <= blk[:, None]) | (blk[:, None] == b)  # block-causal; the last block sees everything
    keep = pack_keep(jnp.asarray(sees))[None].repeat(ids.shape[0], axis=0)
    cos, sin = rope_tables(n, cfg.head_dim, cfg.rope_theta)

    def qkv_part(x, layer, cos, sin):
        h = rms_norm(x, layer["attn_norm"], cfg.norm_eps).astype(cfg.dtype)
        return gqa_experts.qkv_heads(h, layer, gqa_experts.by_batch(cos), gqa_experts.by_batch(sin), cfg)

    out_part = lambda x, o, layer, rng: (gqa_experts.out_and_experts(x, o, layer, cfg)[0], 0.0)
    attend = lambda q, k, v, attention_fn, mesh: (xla_attention(q, k, v, causal=False, keep=keep),)
    x = params["embed"].astype(cfg.dtype)[ids]
    x, _ = jax.lax.scan(lambda x, layer: block(x, layer, cfg, qkv_part, out_part, streams=(cos[:, None], sin[:, None]),
                                               attend=attend), x, params["blocks"])
    got = lm_head(x[:, b * size:], lambda x: rms_norm(x, params["final_norm"], cfg.norm_eps), params["lm_head"], cfg.dtype)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ------------------------------------------------------------------ the draw
def test_the_draw_masks_a_token_with_its_blocks_level_and_weighs_it_by_the_inverse():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import sdar as model

    cfg = model.SdarConfig.nano()
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 254, (64, 4096), dtype=np.int32))
    noised, masked, weight = jax.device_get(model.noise(tokens, jax.random.PRNGKey(2), cfg))
    tokens = np.asarray(tokens)
    assert np.array_equal(noised, np.where(masked, cfg.mask_token_id, tokens))
    assert np.all(weight[~masked] == 0) and weight[masked].min() >= 1.0 and weight[masked].max() <= 1.0 / cfg.noise_eps
    by_block = lambda a: a.reshape(64, -1, cfg.block_length)
    # One level a block: its masked tokens share a weight, 1 / t_b.
    level = by_block(weight).max(axis=-1, keepdims=True)
    assert np.all((by_block(weight) == level) | ~by_block(masked))
    t = 1.0 / level[level > 0]
    assert t.min() >= cfg.noise_eps and t.max() <= 1.0
    # A token is masked with probability t_b at weight 1 / t_b: the weight is 1 in expectation,
    # and half the tokens are masked at t ~ U(eps, 1).
    assert abs(weight.mean() - 1.0) < 0.03 and abs(masked.mean() - 0.5) < 0.01
    t_of_block = np.where(level[..., 0] > 0, 1.0 / np.maximum(level[..., 0], 1e-9), np.nan)
    share = by_block(masked).mean(axis=-1)
    # (a block's level is read off a masked token of it, so a block with none is not among these: a quarter at least)
    assert share[t_of_block > 0.9].mean() > 0.9 and share[t_of_block < 0.1].mean() < 0.3
    again = model.noise(jnp.asarray(tokens), jax.random.PRNGKey(2), cfg)
    other = model.noise(jnp.asarray(tokens), jax.random.PRNGKey(3), cfg)
    assert np.array_equal(np.asarray(again[1]), masked) and not np.array_equal(np.asarray(other[1]), masked)


def test_weights_are_an_objectives_own_and_a_mask_is_weights_normalised_by_their_sum():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.stack import causal_lm_loss

    logits = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16))
    targets = jnp.asarray(np.random.default_rng(0).integers(0, 16, (2, 8)))
    mask = jnp.asarray(np.random.default_rng(1).random((2, 8)) < 0.5)
    plain, masked = causal_lm_loss(logits, targets), causal_lm_loss(logits, targets, mask)
    assert float(causal_lm_loss(logits, targets, weights=jnp.ones((2, 8)))) == pytest.approx(float(plain), rel=1e-6)
    by_weights = causal_lm_loss(logits, targets, weights=mask.astype(jnp.float32)) * mask.size / mask.sum()
    assert float(by_weights) == pytest.approx(float(masked), rel=1e-6)
    with pytest.raises(AssertionError):
        causal_lm_loss(logits, targets, mask, weights=jnp.ones((2, 8)))


# ------------------------------------------------------------------ the shares of a layer
def test_the_shares_of_the_eight_chips_add_up_to_the_uncut_layer():
    """A layer of 16 experts, 2 held a chip: the eight shares' partial sums, each computed where its experts are
    (`gqa_experts.out_and_experts` with `first_expert_held`), add up to the uncut layer written out plainly."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gqa_experts, sdar as model
    from ray_tpu.models.llama import rms_norm

    whole = model.SdarConfig.nano(n_experts_held=None, first_expert_held=0, dtype=jnp.float32)
    whole = model.SdarConfig(**{**whole.__dict__, "n_experts": 16})
    layer = jax.tree.map(lambda a: a[0], model.init_params(whole, jax.random.PRNGKey(0))["blocks"])
    layer["moe"] = {name: w * 10 for name, w in layer["moe"].items()}  # shares that differ by more than rounding
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, whole.d_model))
    o = jnp.zeros((1, whole.n_head, 64, whole.head_dim))
    # The uncut reference: every expert on every position, weighted by the routing matrix.
    h = rms_norm(x, layer["mlp_norm"], whole.norm_eps)[0]
    scores = jax.nn.softmax(h @ layer["moe"]["router_w"], axis=-1)
    chosen = jax.nn.one_hot(jax.lax.top_k(scores, whole.experts_per_token)[1], 16, dtype=bool).any(axis=1)
    weights = jnp.where(chosen, scores, 0.0)
    weights = weights / weights.sum(-1, keepdims=True)
    want = sum(weights[:, e:e + 1] * ((jax.nn.silu(h @ layer["moe"]["w_gate"][e]) * (h @ layer["moe"]["w_up"][e]))
                                      @ layer["moe"]["w_down"][e]) for e in range(16))
    total, pairs = 0.0, 0
    for first in range(0, 16, 2):
        share = model.SdarConfig(**{**whole.__dict__, "n_experts_held": 2, "first_expert_held": first})
        mine = {**layer, "moe": {name: (w if name == "router_w" else w[first:first + 2])
                                 for name, w in layer["moe"].items()}}
        out, aux = gqa_experts.out_and_experts(x, o, mine, share)
        total, pairs = total + (out - x)[0], pairs + int(aux["held_pairs"])
    assert pairs == 64 * whole.experts_per_token
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=2e-5)


def test_a_tiled_router_sends_every_chip_its_even_share_whatever_the_input():
    """`benchmark/models/sdar.py tile_routers` (the configuration's `router_init_tiles` T, applied by `System`
    after `create_train_state`): a router's columns drawn for E / T experts and repeated; with T chips of E / T
    experts a position's choices are the T copies of its best columns, one a chip, so a quarter of the positions
    carrying one embedding (the mask token's) move no chip's load. Where the benchmark's run starts, and no
    option of the model's: the routing's mathematics is untouched."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import sdar as model
    from ray_tpu.models.training import TrainState

    tokens = jnp.asarray(np.random.default_rng(4).integers(0, 254, (2, 64), dtype=np.int32))
    for first in (0, 2, 4, 6):
        cfg = dataclasses.replace(model.SdarConfig.nano(n_experts_held=2, first_expert_held=first), experts_per_token=4)
        plain = model.init_params(cfg, jax.random.PRNGKey(first))
        state = bench.tile_routers(TrainState(params=plain, opt_state=(), step=0), 4)
        router = np.asarray(state.params["blocks"]["moe"]["router_w"])
        assert np.array_equal(router[..., :2], router[..., 2:4]) and np.array_equal(router[..., :2], router[..., 6:])
        assert np.array_equal(router[..., :2], np.asarray(plain["blocks"]["moe"]["router_w"])[..., :2])
        others = lambda params: [x for path, x in jax.tree_util.tree_leaves_with_path(params) if "router_w" not in str(path)]
        assert all(a is b for a, b in zip(others(state.params), others(plain)))  # nothing else of the state changes
        noised, masked, _ = model.noise(tokens, jax.random.PRNGKey(1), cfg)
        stats = model.routing_stats(state.params, tokens, noised, cfg)
        assert np.all(np.asarray(stats["held_pairs"]) == 2 * tokens.size * 4 // 4) and int(stats["dropped"].sum()) == 0
    assert not hasattr(model.SdarConfig.nano(), "router_tiles")


def test_the_benchmarks_system_starts_from_tiled_routers(nano, bf16):
    router = np.asarray(bf16.state.params["blocks"]["moe"]["router_w"])
    assert nano["router_init_tiles"] == 2 and np.array_equal(router[..., :4], router[..., 4:])
    assert int(bf16.state.step) == 0


# ------------------------------------------------------------------ the tree and the trainer
def test_the_initialised_tree_has_the_counted_parameters_and_its_axes():
    import jax

    from ray_tpu.models import sdar as model
    from ray_tpu.models.training import model_for

    cfg = model.SdarConfig.nano()
    shapes = jax.eval_shape(lambda: model.init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)) == model.num_params(cfg)
    axes = model.param_logical_axes(cfg)
    is_axes = lambda x: isinstance(x, tuple)
    assert jax.tree.structure(shapes) == jax.tree.structure(axes, is_leaf=is_axes)
    assert all(len(a) == len(s.shape) for a, s in zip(jax.tree.leaves(axes, is_leaf=is_axes), jax.tree.leaves(shapes)))
    assert axes["blocks"]["moe"]["w_gate"][:2] == ("layers", "expert") and model_for(cfg) is model
    published = model.SdarConfig(n_layer=5, n_experts_held=16, vocab_size=18992, mask_token_id=18990)
    assert model.num_params(published) == 550_984_960  # the issue's 551.0 M
    assert model.kept_pairs(8192, 4) == 8192 * 8192 + 8192 * 4 == 67_141_632


def test_the_trainer_trains_it_and_hands_it_a_key_of_the_step(nano, tokens):
    """No branch by model in `make_train_step`: the key folded from `state.step` reaches `loss_fn`, the same
    state draws the same noise, and the next step another."""
    import jax

    from ray_tpu.models import create_train_state, default_optimizer, make_train_step

    cfg = bench.model_config(nano)
    opt = default_optimizer(learning_rate=1e-3)
    state = create_train_state(cfg, jax.random.PRNGKey(0), opt)
    step = make_train_step(cfg, opt, donate=False)
    first, out = step(state, {"tokens": tokens})
    _, again = step(state, {"tokens": tokens})
    assert float(out["loss"]) == float(again["loss"])
    _, other = step(jax.tree.map(lambda x: x, state).__class__(state.params, state.opt_state, state.step + 1),
                    {"tokens": tokens})
    assert float(other["loss"]) != float(out["loss"])  # the same parameters under the next step's draw
    losses = [float(out["loss"])]
    for _ in range(3):
        first, out = step(first, {"tokens": tokens})
        losses.append(float(out["loss"]))
    assert all(np.isfinite(losses))


def test_a_model_that_draws_nothing_ignores_the_steps_key():
    """GPT-2 with no dropout: the step's loss is the keyless `loss_fn`'s."""
    import jax
    import jax.numpy as jnp

    from ray_tpu import models
    from ray_tpu.models import create_train_state, default_optimizer, make_train_step

    cfg = models.GPTConfig.nano() if hasattr(models.GPTConfig, "nano") else models.GPTConfig.gpt2_small()
    opt = default_optimizer(learning_rate=1e-3)
    state = create_train_state(cfg, jax.random.PRNGKey(0), opt)
    batch = {"tokens": jnp.asarray(np.random.default_rng(0).integers(0, 200, (2, 33), dtype=np.int32))}
    want = float(jax.jit(lambda p: models.loss_fn(p, batch, cfg))(state.params))
    _, out = make_train_step(cfg, opt, donate=False)(state, batch)
    assert float(out["loss"]) == pytest.approx(want, rel=1e-6)


# ------------------------------------------------------------------ the cell's rehearsal
def test_the_cells_cpu_rehearsal_prints_the_contracts_line():
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "2147493039",
         "--seconds", "2", "--trace", "1", "--rehearse-cpu"],
        cwd=REPO, capture_output=True, text=True, timeout=400)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 3
    assert line["device"]["platform"] == "cpu" and "platform=cpu" in proc.stdout
    assert all(name.startswith("rehearsal.") for name in line["metrics"])
    assert f"rehearsal.data.wait_ms.{CONFIG}" in line["metrics"]
    assert line["metrics"][f"rehearsal.moe.load_max_over_mean.{CONFIG}"]["value"] >= 1.0
    # The walk is read off the traced kernels' names, and the CPU runs none: the line leaves the entry out. What the
    # schedule would walk, the draw's share and the held share stay in the check's summary.
    assert "rehearsal.bd.walked_over_live_tiles" not in line["metrics"]
    assert '"dropped": 0' in proc.stdout and '"walked_over_live_tiles": 1.0' in proc.stdout
    assert '"masked_share": ' in proc.stdout and '"held_pairs_share": ' in proc.stdout and '"draw_faults": []' in proc.stdout
