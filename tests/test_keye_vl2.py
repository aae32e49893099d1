"""Keye-VL-2.0's language model through `create_train_state` / `make_train_step`
against the plain float32 reference of `benchmark/models/keye_vl2.py`, at nano
size on the CPU (two layers, hidden 64, 4 query heads on 2 key/value heads of
16, an indexer of 2 heads of 8 that selects 24 keys of a row of 64, a router
over 8 experts of which this share holds 4, 2 a token); on the chip the same
comparison runs at the published widths.

Beside it: `topk >= S` is the dense grouped-query model; the indexer's leaves
learn from `L_I` alone and nothing else learns from it; three unequal position
components against the reference and equal ones against plain rotary; the
shares of the expert layer add up; and the negative cases that say what the
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
from benchmark.models import keye_vl2 as bench  # noqa: E402

CONFIG, CELL = "keye-vl-2.0-30b-a3b-ep8", "keye-vl-2.0-30b-a3b-ep8.fed16k"


@pytest.fixture(scope="module")
def nano():
    return Manifest().config("keye-vl2-nano")


@pytest.fixture(scope="module")
def tokens():
    import jax.numpy as jnp

    return jnp.asarray(np.random.default_rng(0).integers(0, 255, (2, 65), dtype=np.int32))


@pytest.fixture(scope="module")
def f32(nano):
    """The nano configuration computed in float32 throughout, and its seeded parameters."""
    import jax

    from ray_tpu.models import keye_vl2 as model

    c = {**nano, "dtype": "float32"}
    cfg = bench.model_config(c)
    return c, cfg, jax.jit(lambda key: model.init_params(cfg, key))(jax.random.PRNGKey(7))


# The bf16 system built once; `check` keeps the system's side of the comparison for it: the program reads
# `system.cfg` and the reference `system.c`, so the three negative cases, copies with another `c`, read the
# same outputs (`tests/shared_checks.py`).
@pytest.fixture(scope="module")
def bf16(nano):
    return bench.build(nano, None, 3)


@pytest.fixture(scope="module")
def check():
    return shared_checks.Checked(bench)


def _system_loss(params, tokens, cfg, positions=None):
    from ray_tpu.models import keye_vl2 as model
    from ray_tpu.models.stack import causal_lm_loss

    logits, aux = model.forward(params, tokens[:, :-1], cfg, return_aux=True, positions=positions)
    return causal_lm_loss(logits, tokens[:, 1:]) + aux


# ------------------------------------------------------------------ system against reference
def test_the_bf16_system_is_within_the_written_tolerance_of_the_reference(bf16, check, nano, tokens):
    out = check(bf16, tokens)
    assert out["ok"], out
    assert out["routing"]["dropped"] == 0 and out["state_dtypes_other_than_stated"] == []
    limits = nano["check_tolerances"]  # the toy's own: the model file's are the published widths'
    assert out["index_loss_system"] > 0.01 and out["index_loss_rel_err"] < limits["index_loss_rel"]
    assert out["selection_differs_share"] <= limits["selection_differs"] and out["selected_keys_per_query_err"] < 0.2
    assert 0 < out["selection"]["selected_share"] <= 1 and out["selection"]["live_tiles_share"] == 1.0


def test_in_float32_they_agree_to_rounding_by_loss_and_leaf(f32, tokens):
    import jax

    from ray_tpu.models import keye_vl2 as model

    c, cfg, params = f32
    mine, mine_g = jax.jit(jax.value_and_grad(lambda p: model.loss_fn(p, {"tokens": tokens}, cfg)))(params)
    (theirs, aux), theirs_g = jax.jit(jax.value_and_grad(
        lambda p: bench.reference_loss(p, tokens, c), has_aux=True))(params)
    assert abs(float(mine) - float(theirs)) < 2e-5
    flat = lambda g: {jax.tree_util.keystr(k): v for k, v in jax.tree_util.tree_leaves_with_path(g)}
    for name, g in flat(theirs_g).items():
        np.testing.assert_allclose(np.asarray(flat(mine_g)[name]), np.asarray(g), atol=3e-6, rtol=2e-3, err_msg=name)
    stats = model.selection_stats(params, tokens, cfg)
    assert float(stats["index_loss"].sum()) == pytest.approx(float(aux["index_loss"]), rel=1e-4)
    assert float(stats["selected_pairs"].sum()) == float(aux["selected_pairs"].sum())


def test_three_unequal_position_components_against_the_reference(f32, tokens):
    import jax.numpy as jnp

    c, cfg, params = f32
    rng = np.random.default_rng(5)
    positions = jnp.asarray(np.sort(rng.integers(0, 200, (3, 2, 64)), axis=-1).astype(np.int32))
    mine = float(_system_loss(params, tokens, cfg, positions))
    theirs = float(bench.reference_loss(params, tokens, c, positions=positions)[0])
    text = float(_system_loss(params, tokens, cfg))
    assert abs(mine - theirs) < 2e-5 and abs(mine - text) > 1e-4  # the components reach the loss


def test_equal_position_components_are_plain_rotary():
    import jax.numpy as jnp

    from ray_tpu.models import keye_vl2 as model
    from ray_tpu.models.llama import rope_tables

    cfg = model.KeyeVL2Config()  # the published sections, 16 / 24 / 24 of 64 pairs
    tokens = jnp.zeros((1, 96), jnp.int32)
    cos, sin, cos_i, sin_i = model.rope_streams(model.text_positions(tokens), cfg)
    plain_cos, plain_sin = rope_tables(96, cfg.head_dim, cfg.rope_theta)
    np.testing.assert_allclose(np.asarray(cos[:, 0]), np.asarray(plain_cos), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(sin[:, 0]), np.asarray(plain_sin), rtol=1e-6, atol=1e-7)
    index_cos, _ = rope_tables(96, cfg.index_head_dim, cfg.rope_theta)
    np.testing.assert_allclose(np.asarray(cos_i[:, 0]), np.asarray(index_cos), rtol=1e-6)
    # Unequal components: pair 0 turns by the first, pair 16 by the second, pair 40 by the third.
    positions = jnp.stack([jnp.full((1, 96), p) for p in (1, 2, 3)])
    cos = model.rope_streams(positions, cfg)[0]
    freqs = cfg.rope_theta ** (-np.arange(64) / 64)
    for pair, component in ((0, 1), (15, 1), (16, 2), (39, 2), (40, 3), (63, 3)):
        assert float(cos[0, 0, pair]) == pytest.approx(np.cos(component * freqs[pair]), rel=1e-5)


# ------------------------------------------------------------------ the switch
def test_topk_of_the_row_length_is_the_dense_grouped_query_model(f32, tokens):
    from ray_tpu.models import keye_vl2 as model

    _, cfg, params = f32
    whole = dataclasses.replace(cfg, index_topk=64)
    dense = dataclasses.replace(cfg, index_topk=None)
    without = {**params, "blocks": {k: v for k, v in params["blocks"].items() if k != "indexer"}}
    assert set(model.init_params(dense, __import__("jax").random.PRNGKey(0))["blocks"]) == set(without["blocks"])
    a = model.forward(params, tokens[:, :-1], whole)
    b = model.forward(without, tokens[:, :-1], dense)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    kept = model.forward(params, tokens[:, :-1], cfg)  # 24 of 64: another function
    assert float(np.abs(np.asarray(kept) - np.asarray(b)).max()) > 1e-4
    stats = model.selection_stats(params, tokens, whole)
    assert float(stats["selected_pairs"][0]) == float(stats["causal_pairs"][0]) == 2 * 64 * 65 / 2


def test_the_indexer_learns_from_its_loss_alone_and_nothing_else_learns_from_it(f32, tokens):
    import jax

    from ray_tpu.models import keye_vl2 as model

    _, cfg, params = f32
    grad = lambda weight: jax.grad(lambda p: model.loss_fn(
        p, {"tokens": tokens}, dataclasses.replace(cfg, index_loss_weight=weight)))(params)
    with_it, without = grad(1.0), grad(0.0)
    for name, g in without["blocks"]["indexer"].items():
        assert float(np.abs(np.asarray(g)).max()) == 0.0, name  # CE and the load balance never reach it
    for name, g in with_it["blocks"]["indexer"].items():
        assert float(np.abs(np.asarray(g)).max()) > 0.0, name
    rest = lambda g: {**g, "blocks": {k: v for k, v in g["blocks"].items() if k != "indexer"}}
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
                 rest(with_it), rest(without))


def test_the_selection_is_a_choice_of_topk_keys_of_the_past(f32, tokens):
    from ray_tpu.models import keye_vl2 as model
    from ray_tpu.ops.flash_attention import unpack_keep

    _, cfg, params = f32
    stats = model.selection_stats(params, tokens, cfg)
    kept = np.asarray(unpack_keep(stats["keep"], 64))  # (layers, batch, queries, keys)
    assert kept.shape == (2, 2, 64, 64) and not np.triu(kept, 1).any()
    per_query = kept.sum(-1)
    assert (per_query[..., :24] == np.arange(1, 25)).all()  # every key of a past no longer than topk
    assert (per_query[..., 24:] >= 24).all() and int(stats["keys_per_query_min"].min()) == 1
    assert model.selected_pairs(64, 24) == 24 * 25 // 2 + 40 * 24 <= per_query[0, 0].sum()
    assert model.selected_pairs(16384, 2048) == 31_458_304 and model.selected_pairs(2048, 2048) == 2048 * 2049 // 2


# ------------------------------------------------------------------ what the comparison can see
@pytest.mark.parametrize("name, wrong", [
    ("index_loss_weight", lambda c: {**c, "index_loss_weight": 0.0}),
    ("topk", lambda c: {**c, "sa_config": {**c["sa_config"], "topk": 23}}),
    ("aux_loss_weight", lambda c: {**c, "aux_loss_weight": 0.1}),
])
def test_a_reference_of_another_function_fails_the_comparison(bf16, check, nano, tokens, monkeypatch, name, wrong):
    monkeypatch.setattr(bf16, "c", wrong(nano))  # the reference reads `system.c`, the program `system.cfg`
    out = check(bf16, tokens)
    assert not out["ok"], (name, out)


def test_parameters_kept_in_bf16_fail_the_check(bf16, check, nano, tokens, monkeypatch):
    out = bench.check(bench.build({**nano, "param_dtype": "bfloat16"}, None, 3),
                      tokens, loss_tol=1.0, grad_tol=1.0, index_loss_tol=1.0, flipped_tol=1.0, selection_tol=1.0,
                      selected_tol=1.0)
    assert out["state_dtypes_other_than_stated"] == [] and out["ok"]  # stated bf16, kept bf16: consistent
    monkeypatch.setattr(bf16, "c", {**nano, "param_dtype": "bfloat16"})
    out = check(bf16, tokens)
    assert out["state_dtypes_other_than_stated"] == ["float32"] and not out["ok"]


# ------------------------------------------------------------------ the shares
@pytest.mark.parametrize("held", [16, 32])
def test_the_shares_of_the_expert_layer_sum_to_the_uncut_layer(held):
    """128 experts, 8 a token: the partial sums of 128 / held shares, each over its own experts, are the whole layer's."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.moe import moe_mlp

    d, f, n, k = 32, 16, 128, 8
    keys = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(keys[0], (1, 64, d))
    router = jax.random.normal(keys[1], (d, n))
    w_gate, w_up = (jax.random.normal(key, (n, d, f)) * 0.2 for key in keys[2:4])
    w_down = jax.random.normal(keys[4], (n, f, d)) * 0.2
    whole, aux = moe_mlp(x, router, w_gate, w_up, w_down, k=k, norm_topk_prob=True)
    total, pairs = jnp.zeros_like(whole), 0
    for first in range(0, n, held):
        part, aux = moe_mlp(x, router, w_gate[first:first + held], w_up[first:first + held],
                            w_down[first:first + held], k=k, norm_topk_prob=True, held_from=first)
        total, pairs = total + part, pairs + int(aux["held_pairs"])
        assert int(aux["held_pairs"]) == int(aux["rows_processed"])
    assert pairs == 64 * k
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=2e-5)


# ------------------------------------------------------------------ the tree and the trainer
def test_the_initialised_tree_has_the_counted_parameters_and_its_axes():
    import jax

    from ray_tpu.models import keye_vl2 as model
    from ray_tpu.models.training import model_for

    for cfg in (model.KeyeVL2Config.nano(), model.KeyeVL2Config.nano(index_topk=None)):
        shapes = jax.eval_shape(lambda: model.init_params(cfg, jax.random.PRNGKey(0)))
        assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)) == model.num_params(cfg)
        axes = model.param_logical_axes(cfg)
        is_axes = lambda x: isinstance(x, tuple)
        assert jax.tree.structure(shapes) == jax.tree.structure(axes, is_leaf=is_axes)
        assert all(len(a) == len(s.shape) for a, s in zip(
            jax.tree.leaves(axes, is_leaf=is_axes), jax.tree.leaves(shapes)))
        assert axes["blocks"]["moe"]["w_gate"][:2] == ("layers", "expert") and model_for(cfg) is model
    published = model.KeyeVL2Config(n_layer=5, n_experts_held=16, vocab_size=18992)
    assert model.num_params(published) == 562_290_560  # the issue's 562.3 M


def test_the_trainer_trains_it_with_no_branch_by_model(nano, tokens):
    from ray_tpu.models import create_train_state, default_optimizer, make_train_step

    import jax

    cfg = bench.model_config(nano)
    opt = default_optimizer(learning_rate=1e-3)
    state = create_train_state(cfg, jax.random.PRNGKey(0), opt)
    step = make_train_step(cfg, opt)
    losses = []
    for _ in range(4):
        state, out = step(state, {"tokens": tokens})
        losses.append(float(out["loss"]))
    assert losses[-1] < losses[0] and all(np.isfinite(losses))


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
    assert 0.3 < line["metrics"]["rehearsal.dsa.selected_share"]["value"] <= 1.0
    assert line["metrics"]["rehearsal.dsa.live_tiles_share"]["value"] == 1.0
    assert 0.0 < line["metrics"][f"rehearsal.moe.held_pairs_share.{CONFIG}"]["value"] < 0.8
    assert '"dropped": 0' in proc.stdout and "selection_differs_share" in proc.stdout
