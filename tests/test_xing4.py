"""Xing4.0 on the CPU at `nano`: the program against the plain reference on seeded weights (float32: the same
function; bf16: inside the toy's limits), the three parts of the stream maps each told when left out, the shares of an
expert layer adding up to the uncut layer, YaRN's tables against the written formulas, and what the train-state
factory needs of the module."""

import dataclasses
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import xing4 as bench
from ray_tpu.models import glm4_moe_lite as glm
from ray_tpu.models import xing4
from ray_tpu.models.llama import Yarn, rope_tables
from ray_tpu.models.training import model_for
from ray_tpu.models.xing4 import Xing4Config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))
from xing4_readings import in_the_programs_place  # noqa: E402
TIGHT = {"loss_abs": 2e-5, "grad_norm_rel": 1e-4, "leaf_grad_rel": 5e-3, "flipped_share": 0.0, "res_sum_err": 0.05}


def nano_file(**changes):
    """The rehearsal's toy with one expert layer in place of two (a third less to compile: six gradient programs here)."""
    with open(os.path.join(REPO, "benchmark", "configs", "xing4-nano.json")) as fh:
        return {**json.load(fh), "num_hidden_layers": 2, **changes}


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(3), (2, 33), 0, 255)


@pytest.fixture(scope="module")
def exact(tokens):
    """(the float32 program's system under limits a rounding would pass, the reference's program, what it gave)."""
    system = bench.build(nano_file(dtype="float32", check_tolerances=TIGHT), None, 7)
    of_reference = jax.jit(bench.losses_and_grads(system)[1])
    return system, of_reference, of_reference(system.state.params, tokens)


def like(system, **changes):
    """`system` with some attributes changed: the fixture's own stays as it was."""
    other = bench.System.__new__(bench.System)
    other.__dict__.update(system.__dict__, **changes)
    return other


def stands_in(system, **faults):
    """The reference under `faults` as `check(program=)` takes one: `tools/xing4_readings.py`'s own."""
    return in_the_programs_place(system, bench.losses_and_grads(system, **faults)[1])


def test_the_float32_program_is_the_reference(exact, tokens):
    system, _, reference = exact
    out = bench.check(system, tokens, reference=reference)
    assert out["ok"] and out["over_limit"] == [] and out["expert_choices_flipped_share"] == 0.0, out
    assert set(out["leaf_grad_rel_err"]) == set(bench.CHECKED_LEAVES) == set(bench.LEAF_GRAD_REL_TOL)
    assert min(out["leaf_grad_norm_reference"].values()) > 1e-7  # every checked leaf reaches the loss
    assert len(out["streams"]["res_sum_err_by_layer"]) == 2 and out["routing"]["dropped"] == 0
    assert out["res_sum_err"] == pytest.approx(out["streams"]["res_sum_err_reference"], rel=1e-2)


@pytest.mark.parametrize("fault,told_by", [
    ({"dynamic": False}, ("leaf_grad_rel_err.a_pre", "leaf_grad_rel_err.a_post", "leaf_grad_rel_err.a_res", "leaf_grad_rel_err.hc_attn.phi")),
    ({"static": False}, ("leaf_grad_rel_err.b_pre", "leaf_grad_rel_err.b_post", "leaf_grad_rel_err.b_res")),
    ({"rounds": 1}, ("res_sum_err", "leaf_grad_rel_err.a_res", "leaf_grad_rel_err.b_res")),
])
def test_a_part_of_the_maps_left_out_fails_a_limit_of_the_cells_own(exact, tokens, fault, told_by):
    """Under the published widths' limits, not the toy's: a_* = 0, the biases 0, one round in place of twenty."""
    system, _, reference = exact
    system = like(system, c={k: v for k, v in system.c.items() if k != "check_tolerances"})
    out = bench.check(system, tokens, reference=reference, program=stands_in(system, **fault))
    assert not out["ok"] and set(told_by) <= set(out["over_limit"]), out["over_limit"]


def test_no_clamp_is_told_where_a_logit_passes_thirty(exact, tokens):
    """The program's own clamp at such inputs: `tests/test_hyper_connections.py`; the cell's bf16 program inside the
    toy's limits: `tests/test_xing4_rehearsal.py`."""
    system, of_reference, _ = exact
    far = lambda hc: {**hc, "bias": hc["bias"].at[..., 8].set(100.0)}  # noqa: E731  B_res[0, 0]: exp(100) is no float32
    params = jax.tree.map(lambda a: a, system.state.params)
    params["blocks"]["leading"][0]["hc_attn"] = far(params["blocks"]["leading"][0]["hc_attn"])
    params["blocks"]["period"][0]["hc_ffn"] = far(params["blocks"]["period"][0]["hc_ffn"])
    system = like(system, state=dataclasses.replace(system.state, params=params))
    reference = of_reference(params, tokens)
    assert math.isfinite(float(reference[0])) and float(reference[3]["res_sum_err"]) < bench.RES_SUM_ERR_TOL
    out = bench.check(system, tokens, reference=reference, program=stands_in(system, clamp=False))
    assert not out["ok"] and not math.isfinite(out["loss_system"])


def test_the_shares_add_up_to_the_uncut_layer():
    """Four shares of two of the toy's eight experts: their routed parts, with the shared expert counted once, are the
    uncut layer's output, and the uncut layer is every expert on every token under the reference's routing matrix."""
    whole = Xing4Config.nano(dtype=jnp.float32, n_experts_held=8, first_expert_held=0)
    layer = jax.tree.map(lambda a: a[0], xing4.init_params(whole, jax.random.PRNGKey(5))["blocks"]["period"][0])
    u = jax.random.normal(jax.random.PRNGKey(6), (2, 16, 64))
    routed, shared, _ = jax.jit(lambda u, layer: glm.moe_ffn(u, layer, whole))(u, layer)
    parts = []
    for first in (0, 2, 4, 6):
        share = dataclasses.replace(whole, n_experts_held=2, first_expert_held=first)
        held = {**layer, "moe": {**layer["moe"], **{name: layer["moe"][name][first:first + 2] for name in ("w_gate", "w_up", "w_down")}}}
        part, shared_again, _ = jax.jit(lambda u, held, share=share: glm.moe_ffn(u, held, share))(u, held)
        np.testing.assert_allclose(shared_again, shared, rtol=1e-6)  # every chip computes it alike: counted once
        parts.append(part)
    np.testing.assert_allclose(sum(parts), routed, rtol=1e-4, atol=1e-6)
    # ... and the uncut layer against plain jnp
    h = (u / jnp.sqrt((u * u).mean(-1, keepdims=True) + whole.norm_eps) * layer["ffn_norm"]).reshape(-1, 64)
    moe = layer["moe"]
    weights, _ = bench.routing_matrix(jax.nn.sigmoid(h @ moe["router_w"]), moe["expert_bias"], 2, True, 2.0)
    every = sum(weights[:, e, None] * ((jax.nn.silu(h @ moe["w_gate"][e]) * (h @ moe["w_up"][e])) @ moe["w_down"][e])
                for e in range(8))
    np.testing.assert_allclose(routed.reshape(-1, 64), every, rtol=2e-3, atol=1e-5)


def test_yarns_tables_are_the_written_formulas():
    published = Xing4Config()
    yarn = published.yarn
    assert yarn == Yarn(64.0, 4096, 32.0, 1.0, 1.0, 1.0) and yarn.correction_range(64, 1e4) == (10, 23)
    freqs = np.asarray(yarn.frequencies(64, 1e4), np.float64)
    plain = 1e4 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(freqs[:11], plain[:11], rtol=1e-6)  # turned 32 times and more in 4,096: as published
    np.testing.assert_allclose(freqs[23:], plain[23:] / 64, rtol=1e-6)  # less than once: stretched to the new length
    np.testing.assert_allclose(freqs[11:23], plain[11:23] * (1 - (np.arange(11, 23) - 10) / 13 * (1 - 1 / 64)), rtol=1e-5)
    assert yarn.table_scale == 1.0 and yarn.softmax_scale == pytest.approx((0.1 * math.log(64) + 1) ** 2)
    assert published.softmax_scale == pytest.approx(192 ** -0.5 * 1.4159 ** 2, rel=1e-4)
    with open(os.path.join(REPO, "benchmark", "configs", "xing4-29b-a4b-ep8-l5.json")) as fh:
        c = json.load(fh)
    cos, sin, scale = bench.yarn_tables(c, 4096)  # the reference's own, in numpy float64
    got_cos, got_sin = rope_tables(4096, 64, 1e4, yarn)
    assert scale == pytest.approx(published.softmax_scale, rel=1e-12) and cos.shape == (4096, 64)
    early = slice(0, 64)  # float32 angles of 4,095 radians lose digits that float64 keeps
    np.testing.assert_allclose(got_cos[early], cos[early, :32], atol=2e-5)
    np.testing.assert_allclose(got_sin[early], sin[early, 32:], atol=2e-5)
    np.testing.assert_allclose(got_cos, cos[:, :32], atol=2e-3)
    # no scaling is the tables every other model reads, to the bit
    for a, b in zip(rope_tables(128, 64, 1e4), rope_tables(128, 64, 1e4, None)):
        assert (np.asarray(a) == np.asarray(b)).all()
    assert Yarn(1.0, 4096).softmax_scale == 1.0 and Yarn(4.0, 16).table_scale == pytest.approx(0.1 * math.log(4) + 1)


def test_the_module_is_a_model_of_the_zoo():
    cfg = Xing4Config.nano()
    assert model_for(cfg) is xing4 and (cfg.head_dim, cfg.v_head_dim, cfg.hc_mult) == (32, 16, 4)
    params = jax.eval_shape(lambda: xing4.init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(params)) == xing4.num_params(cfg)
    axes, frozen = xing4.param_logical_axes(cfg), xing4.frozen_params(cfg)
    assert jax.tree.structure(frozen) == jax.tree.structure(params)
    assert axes["blocks"]["period"][0]["hc_ffn"]["phi"] == ("layers", None, None, "embed")
    names = [jax.tree_util.keystr(path) for path, is_buffer in jax.tree_util.tree_flatten_with_path(frozen)[0] if is_buffer]
    assert names and all(name.endswith("['expert_bias']") for name in names)
    with pytest.raises(AssertionError, match="prediction module"):
        Xing4Config.nano(n_predict_layers=1)
    published = Xing4Config()
    assert (published.head_dim, published.v_head_dim, published.n_head, published.d_model) == (192, 128, 32, 3584)
    per_sublayer = 14336 * 24 + 27
    assert xing4.num_params(published) - glm.num_params(published) == 2 * 40 * per_sublayer
    assert xing4.train_flops_per_token(published, 4096) - glm.train_flops_per_token(published, 4096) == 6.0 * 80 * 14336 * 24
    # attention by both widths: 6 x heads x (192 + 128) x seq a call
    assert glm.train_flops_per_token(published, 4096) - glm.train_flops_per_token(published, 0) == 6.0 * 40 * 32 * 320 * 4096


def test_the_streams_are_the_embedding_four_times_and_leave_as_their_sum():
    cfg = Xing4Config.nano(dtype=jnp.float32)
    params = xing4.init_params(cfg, jax.random.PRNGKey(2))
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 16), 0, 256)
    x = xing4.streams_in(params, tokens, cfg)
    assert x.shape == (2, 4, 16, 64) and bool((x[:, 0] == x[:, 3]).all()) and bool((x[:, 1] == params["embed"][tokens]).all())
    assert jax.eval_shape(lambda p: xing4.hidden(p, tokens, cfg), params).shape == (2, 16, 64)
    assert jax.eval_shape(lambda p: xing4.forward(p, tokens, cfg), params).shape == (2, 16, 256)
    assert params["blocks"]["period"][0]["hc_attn"]["phi"].shape == (2, 24, 4, 64)
