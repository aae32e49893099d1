"""OLMoE through `create_train_state` / `make_train_step` against the plain
float32 reference of `benchmark/models/olmoe.py`, at nano size on the CPU
(2 layers, hidden 64, 4 heads of 16, 8 experts top-2 of width 32, 64
positions); on the chip the same comparison runs at the published widths.

The negative cases say what the comparison can see: a reference that
renormalises the top-k weights, norms q and k per head, or drops the tokens
over a capacity is another function, and fails it."""

import dataclasses
import os
import re
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import shared_checks  # noqa: E402
from benchmark.harness.manifest import Manifest  # noqa: E402
from benchmark.models import olmoe as bench_olmoe  # noqa: E402


@pytest.fixture(scope="module")
def nano():
    return Manifest().config("olmoe-nano")


@pytest.fixture(scope="module")
def tokens():
    import jax.numpy as jnp

    return jnp.asarray(np.random.default_rng(0).integers(0, 255, (2, 65), dtype=np.int32))


def _trained(c, tokens, steps=60):
    """Weights that mean something: at seeded initial weights the loss hardly
    depends on what attention and the experts do."""
    system = bench_olmoe.build(dict(c, learning_rate=3e-3), None, 7)
    for _ in range(steps):
        system.state, metrics = system.step(system.state, {"tokens": tokens})
    assert float(metrics["loss"]) < 1.0
    return system


@pytest.fixture(scope="module")
def trained(nano, tokens):
    return _trained(nano, tokens)


@pytest.fixture(scope="module")
def trained_f32(nano, tokens):
    return _trained(dict(nano, dtype="float32"), tokens)


# A bf16 system built once; `check` keeps the system's side of the comparison for it and for
# `trained_f32`, which the three negative cases read (`tests/shared_checks.py`).
@pytest.fixture(scope="module")
def bf16(nano):
    return bench_olmoe.build(nano, None, 7)


@pytest.fixture(scope="module")
def check():
    return shared_checks.Checked(bench_olmoe)


# ------------------------------------------------------------ they agree
def test_the_bf16_system_is_within_the_written_tolerance_of_the_reference(bf16, check, tokens):
    got = check(bf16, tokens)
    assert got["ok"], got
    assert got["loss_abs_err"] < bench_olmoe.LOSS_ABS_TOL
    assert got["grad_norm_rel_err"] < bench_olmoe.GRAD_NORM_REL_TOL
    # Cross entropy near ln(256) plus 0.01 x 2 and 0.001 x ln(8)^2 of auxiliary terms.
    assert abs(got["loss_reference"] - (np.log(256) + 0.02 + 0.0043)) < 0.1
    assert got["routing"]["dropped"] == 0 and got["routing"]["pairs_per_layer"] == 2 * 64 * 2


def test_in_float32_they_agree_to_rounding_and_pick_the_same_experts(nano, tokens):
    """The reference computes the system's function, not one near it."""
    got = bench_olmoe.check(bench_olmoe.build(dict(nano, dtype="float32"), None, 7), tokens)
    assert got["loss_abs_err"] < 2e-6 and got["grad_norm_rel_err"] < 2e-5, got
    assert got["expert_choices_flipped_share"] == 0.0


def test_they_agree_at_trained_weights_too(trained, trained_f32, check, tokens):
    got = check(trained, tokens)
    assert got["ok"], got
    exact = check(trained_f32, tokens)
    assert exact["loss_abs_err"] < 2e-6 and exact["expert_choices_flipped_share"] == 0.0, exact


def test_nothing_is_dropped_under_a_skew_beyond_four_times_the_mean(nano, tokens):
    """A router biased towards one expert: every token carries a common
    component, and expert 0's router column points along it. That expert then
    takes more than four times the mean load (16 experts, 2 a token: at most
    8 times), nothing is dropped, and system and reference still agree."""
    import jax.numpy as jnp

    c = dict(nano, dtype="float32", num_experts=16)
    system = bench_olmoe.build(c, None, 7)
    params = system.state.params
    common = jnp.ones((c["hidden_size"],)) / np.sqrt(c["hidden_size"])
    moe = dict(params["blocks"]["moe"])
    moe["router_w"] = moe["router_w"].at[:, :, 0].add(4.0 * common)
    system.state.params = {**params, "embed": params["embed"] + common,
                           "blocks": {**params["blocks"], "moe": moe}}
    got = bench_olmoe.check(system, tokens)
    assert got["routing"]["load_max_over_mean"] > 4.0, got
    assert got["routing"]["dropped"] == 0
    assert got["routing"]["tokens_per_expert_max"] == 2 * 64  # every token chose it
    assert got["ok"] and got["loss_abs_err"] < 2e-6 and got["expert_choices_flipped_share"] == 0.0, got


# ------------------------------------- another function fails the comparison
def _per_head_qk_norm(q, k, q_scale, k_scale, eps):
    def per_head(x, scale):
        heads = x.reshape(*x.shape[:-1], 4, 16)
        return bench_olmoe.rms_norm(heads, scale.reshape(4, 16), eps).reshape(x.shape)

    return per_head(q, q_scale), per_head(k, k_scale)


def _renormalising(real):
    return lambda probs, k, renormalise: real(probs, k, True)


def _dropping(real):
    """Switch-style capacity 1.0: an expert keeps the first tokens * k / E of
    the tokens that chose it."""
    import jax.numpy as jnp

    def routing_matrix(probs, k, renormalise):
        weights, chosen = real(probs, k, renormalise)
        keep = chosen & (jnp.cumsum(chosen, axis=0) <= probs.shape[0] * k // probs.shape[1])
        return jnp.where(keep, weights, 0.0), keep

    return routing_matrix


@pytest.mark.parametrize("name,wrong", [
    ("routing_matrix", _renormalising), ("routing_matrix", _dropping),
    ("qk_norm", lambda real: _per_head_qk_norm)],
    ids=["renormalised_top_k", "tokens_dropped_over_capacity", "qk_norm_per_head"])
def test_a_reference_of_another_function_fails_the_comparison(trained_f32, check, tokens, monkeypatch,
                                                              name, wrong):
    monkeypatch.setattr(bench_olmoe, name, wrong(getattr(bench_olmoe, name)))
    got = check(trained_f32, tokens)
    assert not got["ok"], got
    assert (got["grad_norm_rel_err"] > 2 * bench_olmoe.GRAD_NORM_REL_TOL
            or got["loss_abs_err"] > 2 * bench_olmoe.LOSS_ABS_TOL), got


def test_the_reference_in_bf16_is_outside_the_loss_tolerance(bf16, nano, tokens):
    """What the nearest precision below the configuration's would give: the
    reference with parameters, router, norms and logits in bf16 (PERF.md
    section 6, PR 28, has the chip's reading at the published widths)."""
    import jax
    import jax.numpy as jnp

    params = bf16.state.params
    exact, chosen = jax.jit(lambda p: bench_olmoe.reference_loss(p, tokens, nano))(params)
    low, low_chosen = jax.jit(
        lambda p: bench_olmoe.reference_loss(p, tokens, nano, dtype=jnp.bfloat16))(params)
    assert abs(float(exact) - float(low)) > bench_olmoe.LOSS_ABS_TOL
    assert 0 < float((chosen != low_chosen).mean())  # and its bf16 router picks other experts


def test_parameters_kept_in_bf16_fail_the_check(bf16, tokens):
    import jax.numpy as jnp

    got = bench_olmoe.check(shared_checks.in_dtype(bf16, jnp.bfloat16), tokens)
    assert not got["ok"] and got["state_dtypes_other_than_stated"] == ["bfloat16"]


def test_norm_topk_prob_renormalises_and_is_off_in_the_configuration(nano):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.moe import route

    assert nano["norm_topk_prob"] is False
    assert Manifest().config("olmoe-1b-7b-l1")["norm_topk_prob"] is False
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    plain, experts, aux = route(x, w, 2)
    normed, same_experts, _ = route(x, w, 2, norm_topk_prob=True)
    probs = jax.nn.softmax(x @ w, axis=-1)
    np.testing.assert_allclose(plain, jnp.take_along_axis(probs, experts, axis=-1), rtol=1e-6)
    assert float(plain.sum(-1).max()) < 1.0  # the softmax's own weights: they do not sum to one
    np.testing.assert_allclose(normed.sum(-1), 1.0, rtol=1e-6)
    assert (experts == same_experts).all() and int(aux["tokens_per_expert"].sum()) == 64


# ------------------------------------------------------ the layer is linear
def _compiled_nano_step(seq, **config):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import OLMoEConfig, create_train_state, default_optimizer, make_train_step

    cfg = dataclasses.replace(OLMoEConfig.nano(), **config)
    opt = default_optimizer()
    state = jax.eval_shape(lambda: create_train_state(cfg, jax.random.PRNGKey(0), opt))
    batch = {"tokens": jax.ShapeDtypeStruct((2, seq + 1), jnp.int32)}
    return make_train_step(cfg, opt).lower(state, batch).compile()


def test_no_array_has_both_a_sequence_and_an_expert_axis_and_cost_is_linear():
    """No `(B, S, E, C)`: at 48 positions and 12 experts no array of rank four
    or more in the compiled step has a 48 and a 12 among its dimensions (the
    Switch layer this replaced held four of `[2, 48, 12, C]`), and twice the
    tokens cost about twice the FLOPs (attention's square is the rest)."""
    text = _compiled_nano_step(48, n_experts=12).as_text()
    shapes = {tuple(int(n) for n in dims.split(","))
              for dims in re.findall(r"(?:f32|bf16|s32|pred)\[([0-9,]+)\]", text)}
    assert len(shapes) > 30
    assert not [s for s in shapes if len(s) >= 4 and 48 in s and 12 in s]
    flops = [_compiled_nano_step(seq).cost_analysis()["flops"] for seq in (32, 64)]
    assert 1.9 < flops[1] / flops[0] < 2.3, flops


# --------------------------------------------------------------- arithmetic
def test_parameters_and_flops_by_hand():
    """One layer: 4 x 2048^2 = 16,777,216 of attention, 2048 x 64 = 131,072 of
    router, 64 x 3 x 2048 x 1024 = 402,653,184 of experts, 4 x 2048 = 8,192 of
    norm scales: 419,569,664. Embedding and untied head 2 x 50,304 x 2048 =
    206,045,184, final norm 2,048. A token meets 16,777,216 + 131,072 + 8 x 3 x
    2048 x 1024 + 50,304 x 2048 = 170,262,528 matmul parameters."""
    from ray_tpu.models import OLMoEConfig
    from ray_tpu.models import olmoe

    one, two = OLMoEConfig(n_layer=1), OLMoEConfig(n_layer=2)
    assert olmoe.num_params(two) - olmoe.num_params(one) == 419_569_664
    assert olmoe.num_params(one) == 625_616_896
    assert olmoe.num_params(OLMoEConfig()) == 206_045_184 + 2_048 + 16 * 419_569_664  # 6.92 B
    assert olmoe.train_flops_per_token(one, 4096) == 6.0 * 170_262_528 + 12 * 2048 * 4096
    assert olmoe.train_flops_per_token(one, 4096) == 1_122_238_464.0


def test_the_initialised_tree_has_the_counted_parameters():
    import jax

    from ray_tpu.models import OLMoEConfig
    from ray_tpu.models import olmoe

    cfg = OLMoEConfig.nano()
    shapes = jax.eval_shape(lambda: olmoe.init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)) == olmoe.num_params(cfg)
    axes = olmoe.param_logical_axes(cfg)
    assert jax.tree.structure(shapes) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    assert axes["blocks"]["moe"]["w_gate"][1] == "expert"


# ------------------------------------------------- the boundary it stands on
def test_the_kernels_boundary_this_configuration_stands_on():
    from ray_tpu.ops.flash_attention import kernel_plan, select_backend

    assert select_backend((2, 16, 4096, 128), "tpu") == "pallas"
    # Until PR 39 the kernels ended here (`s * d <= 8192 * 64`); since then the backward pass
    # streams tile pairs from heads of twice this VMEM on, and the limit is four times it.
    assert select_backend((2, 16, 8192, 128), "tpu") == "pallas"
    # ... and since PR 42 the forward pass streams them too, up to 16,384 x 128; the scan beyond.
    assert select_backend((2, 16, 8192 + 128, 128), "tpu") == "pallas"
    assert select_backend((2, 16, 16384 + 128, 128), "tpu") == "blockwise"
    # Heads of 4096 x 128 walk 512-tiles in the loop form (1024-tiles miss the
    # 16 MiB of VMEM in the backward pass); shorter heads are as they were. A
    # 4096 x 64 head takes the same VMEM (64 lanes pad to 128) and the same form
    # since PR 35: at 1024-tiles `(8, 32, 4096, 64)` did not compile for the v5e.
    assert kernel_plan((2, 16, 4096, 128), True) == (512, 512, 36, 8, 64, False)
    assert kernel_plan((1, 8, 4096, 64), True) == (512, 512, 36, 8, 64, False)
    assert kernel_plan((1, 8, 2048, 64), True) == (512, 512, 10, 4, 16, True)
    assert kernel_plan((8, 16, 1024, 64), True) == (512, 512, 3, 2, 4, True)


def test_the_long_head_form_of_the_kernels_agrees_with_plain_attention():
    """Heads of 4096 x 128: the loop form at 512-tiles, with the backward
    program's whole-head operands in one buffer each (interpret mode)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.flash_attention import flash_attention, xla_attention

    shape = (1, 1, 4096, 128)
    q, k, v, do = (jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)
                   for key in jax.random.split(jax.random.PRNGKey(0), 4))
    out, vjp = jax.vjp(lambda q, k, v: flash_attention(q, k, v, backend="pallas", interpret=True),
                       q, k, v)
    want, want_vjp = jax.vjp(xla_attention, q, k, v)
    for got, ref in zip((out, *vjp(do)), (want, *want_vjp(do))):
        np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(ref, np.float32),
                                   atol=3e-2)  # outputs of order 1 in bf16
