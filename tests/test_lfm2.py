"""LFM2-MoE through `create_train_state` / `make_train_step` against the plain
float32 reference of `benchmark/models/lfm2.py`, at nano size on the CPU (two
dense conv layers, two periods of attention + three gated short convolutions,
hidden 64, 4 query and 2 key/value heads of 16, a router over 8 experts of
which this share holds 2, 2 a token, 64 positions); on the chip the same
comparison runs at the published widths.

Beside it: the patterned stack equal to its layers applied one by one; the
share tied to the model (the eight shares of an expert layer add up to the
whole layer, and their weight gradients are the whole gradient's slices); and
the negative cases that say what the comparison can see: a reference that
renormalises wrongly, lets the selection bias into the weights, norms q and k
over the whole projection or shifts the convolution by one position is
another function, and fails it."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import shared_checks  # noqa: E402
from benchmark.harness.manifest import Manifest  # noqa: E402
from benchmark.models import lfm2 as bench_lfm2  # noqa: E402


@pytest.fixture(scope="module")
def nano():
    return Manifest().config("lfm2-nano")


@pytest.fixture(scope="module")
def tokens():
    import jax.numpy as jnp

    return jnp.asarray(np.random.default_rng(0).integers(0, 255, (2, 65), dtype=np.int32))


def _trained(c, tokens, steps=60):
    """Weights that mean something: at seeded initial weights the loss hardly
    depends on what the operators and the experts do."""
    import jax

    system = bench_lfm2.build(dict(c, learning_rate=3e-3), None, 7)
    # ... and a selection bias as large as a balancing rule would make it: seeded at
    # N(0, 0.02) it hardly changes a choice or a weight, and no step moves it.
    system.state.params = jax.tree_util.tree_map_with_path(
        lambda path, p: p * 200.0 if "expert_bias" in jax.tree_util.keystr(path) else p,
        system.state.params)
    for _ in range(steps):
        system.state, metrics = system.step(system.state, {"tokens": tokens})
    assert float(metrics["loss"]) < 1.5
    return system


@pytest.fixture(scope="module")
def trained_f32(nano, tokens):
    return _trained(dict(nano, dtype="float32"), tokens)


# A system a precision, built once; `check` keeps the system's side of the comparison for each of them
# and for `trained_f32`, which the five negative cases read (`tests/shared_checks.py`).
@pytest.fixture(scope="module")
def bf16(nano):
    return bench_lfm2.build(nano, None, 7)


@pytest.fixture(scope="module")
def f32(nano):
    return bench_lfm2.build(dict(nano, dtype="float32"), None, 7)


@pytest.fixture(scope="module")
def check():
    return shared_checks.Checked(bench_lfm2)


# ------------------------------------------------------------ they agree
def test_the_bf16_system_is_within_the_written_tolerance_of_the_reference(bf16, check, tokens):
    got = check(bf16, tokens)
    assert got["ok"], got
    assert abs(got["loss_reference"] - np.log(256)) < 0.1  # no auxiliary term
    routing = got["routing"]
    assert routing["dropped"] == 0 and routing["pairs_per_layer"] == 2 * 64 * 2
    assert routing["held_pairs"] + routing["elsewhere_pairs"] == 8 * 2 * 64 * 2  # 8 expert layers
    assert 0 < routing["held_pairs_share"] < 0.6  # 2 of 8 experts: 0.25 were the router even


def test_in_float32_they_agree_to_rounding_by_leaf_and_pick_the_same_experts(f32, check, tokens):
    """The reference computes the system's function, not one near it: loss,
    every leaf of the gradient, and every choice."""
    import jax

    from ray_tpu.models import lfm2

    system, c = f32, f32.c
    got = check(system, tokens)
    assert got["loss_abs_err"] < 2e-6 and got["grad_norm_rel_err"] < 2e-5, got
    assert got["expert_choices_flipped_share"] == 0.0
    params = system.state.params
    mine = jax.jit(jax.grad(lambda p: lfm2.loss_fn(p, {"tokens": tokens}, system.cfg)))(params)
    theirs = jax.jit(jax.grad(lambda p: bench_lfm2.reference_loss(p, tokens, c)[0]))(params)
    for (path, a), b in zip(jax.tree.leaves_with_path(mine), jax.tree.leaves(theirs)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-6, err_msg=jax.tree_util.keystr(path))
    bias = mine["blocks"]["period"][1]["moe"]["expert_bias"]  # the period's first conv layer
    assert not np.asarray(bias).any()  # it enters the choice only


def test_they_agree_at_trained_weights_too(trained_f32, check, tokens):
    exact = check(trained_f32, tokens)
    assert exact["loss_abs_err"] < 2e-6 and exact["expert_choices_flipped_share"] == 0.0, exact


def test_no_optimizer_step_moves_the_selection_bias(nano, tokens):
    """`frozen_params`: AdamW's decoupled weight decay would shrink a buffer
    whose gradient is zero; the step applies no update to it."""
    import jax

    system = bench_lfm2.build(dict(nano, learning_rate=3e-3), None, 7)
    before = jax.tree.map(np.asarray, system.state.params)
    for _ in range(3):
        system.state, _ = system.step(system.state, {"tokens": tokens})
    moved = jax.tree.map(lambda a, b: float(np.abs(a - np.asarray(b)).max()), before, system.state.params)
    for path, change in jax.tree.leaves_with_path(moved):
        assert (change == 0.0) == ("expert_bias" in jax.tree_util.keystr(path)), (path, change)


# ------------------------------------- another function fails the comparison
def _qk_norm_over_the_projection(q, k, q_scale, k_scale, eps):
    """OLMoE's: q and k normed over all heads together (the scale tiled)."""
    import jax.numpy as jnp

    def whole(x, scale):
        flat = x.reshape(*x.shape[:-2], -1)
        return bench_lfm2.rms_norm(flat, jnp.tile(scale, x.shape[-2]), eps).reshape(x.shape)

    return whole(q, q_scale), whole(k, k_scale)


def _renormalising_over_all_scores(real):
    """The chosen scores over the sum of every expert's, not of the chosen."""
    def routing_matrix(scores, bias, k, renormalise, scale):
        weights, chosen = real(scores, bias, k, False, scale)
        return weights / scores.sum(-1, keepdims=True), chosen

    return routing_matrix


def _bias_in_the_weights(real):
    def routing_matrix(scores, bias, k, renormalise, scale):
        return real(scores + bias, 0.0 * bias, k, renormalise, scale)

    return routing_matrix


def _shifted_by_one(real):
    import jax.numpy as jnp

    # The taps in reverse order: tap j on position t - j: the window [t, t - 2] read backwards.
    return lambda b, c, u, taps: real(b, c, u, jnp.flip(taps, axis=0))


def _one_position_late(real):
    import jax.numpy as jnp

    def short_conv_mix(b, c, u, taps):
        late = lambda x: jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)
        return c * late(real(b, jnp.ones_like(c), u, taps))

    return short_conv_mix


@pytest.mark.parametrize("name,wrong", [
    ("routing_matrix", _renormalising_over_all_scores), ("routing_matrix", _bias_in_the_weights),
    ("qk_norm", lambda real: _qk_norm_over_the_projection),
    ("short_conv_mix", _shifted_by_one), ("short_conv_mix", _one_position_late)],
    ids=["renormalised_over_all_scores", "selection_bias_in_the_weights", "qk_norm_over_the_projection",
         "convolution_taps_reversed", "convolution_one_position_late"])
def test_a_reference_of_another_function_fails_the_comparison(trained_f32, check, tokens, monkeypatch,
                                                              name, wrong):
    monkeypatch.setattr(bench_lfm2, name, wrong(getattr(bench_lfm2, name)))
    got = check(trained_f32, tokens)
    assert not got["ok"], got
    assert (got["grad_norm_rel_err"] > 2 * bench_lfm2.GRAD_NORM_REL_TOL
            or got["loss_abs_err"] > 2 * bench_lfm2.LOSS_ABS_TOL), got


def test_the_reference_in_bf16_is_outside_a_tolerance(bf16, nano, tokens):
    """What the nearest precision below the configuration's would give: the
    reference with parameters, router, norms and logits in bf16 (PERF.md
    section 6, PR 35, has the chip's reading at the published widths)."""
    import jax
    import jax.numpy as jnp

    params = bf16.state.params
    exact, chosen = jax.jit(lambda p: bench_lfm2.reference_loss(p, tokens, nano))(params)
    low, low_chosen = jax.jit(
        lambda p: bench_lfm2.reference_loss(p, tokens, nano, dtype=jnp.bfloat16))(params)
    assert abs(float(exact) - float(low)) > bench_lfm2.LOSS_ABS_TOL
    assert 0 < float((chosen != low_chosen).mean())  # and its bf16 router picks other experts


def test_parameters_kept_in_bf16_fail_the_check(bf16, tokens):
    import jax.numpy as jnp

    got = bench_lfm2.check(shared_checks.in_dtype(bf16, jnp.bfloat16), tokens)
    assert not got["ok"] and got["state_dtypes_other_than_stated"] == ["bfloat16"]


# ----------------------------------------------------------------- the router
def test_sigmoid_scores_a_bias_for_the_choice_only_and_renormalised_weights():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.moe import route

    x = jax.random.normal(jax.random.PRNGKey(0), (32, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    bias = jnp.zeros((8,)).at[5].set(10.0)  # everyone picks expert 5, whatever its score
    weights, experts, aux = route(x, w, 2, norm_topk_prob=True, bias=bias, scale=2.5)
    scores = jax.nn.sigmoid(x @ w)
    assert (experts == 5).any(axis=-1).all() and int(aux["tokens_per_expert"][5]) == 32
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    np.testing.assert_allclose(weights, 2.5 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    plain, _, _ = route(x, w, 2, bias=bias)
    np.testing.assert_allclose(plain, chosen, rtol=1e-6)  # the scores themselves: no 10 in them
    grad = jax.grad(lambda b: route(x, w, 2, norm_topk_prob=True, bias=b)[0].sum())(bias)
    assert not np.asarray(grad).any()


# ------------------------------------------------- the share tied to the model
def _expert_layer(seed=0, tokens=128, d=32, f=16, n_experts=8):
    import jax

    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    normal = lambda key, shape, std=1.0: jax.random.normal(key, shape) * std
    return {
        "x": normal(keys[0], (2, tokens // 2, d)), "router_w": normal(keys[1], (d, n_experts), 0.5),
        "bias": normal(keys[2], (n_experts,), 0.1), "w_gate": normal(keys[3], (n_experts, d, f), 0.3),
        "w_up": normal(keys[4], (n_experts, d, f), 0.3), "w_down": normal(keys[5], (n_experts, f, d), 0.3),
        "dout": normal(keys[6], (2, tokens // 2, d)),
    }


def _share(p, first, count, k=2):
    """(out, gradients for x and the held experts' matrices) of the layer that
    holds experts `first .. first + count` of the 8 the router scores."""
    import jax

    from ray_tpu.models.moe import moe_mlp

    def layer(x, w_gate, w_up, w_down):
        out, aux = moe_mlp(x, p["router_w"], w_gate, w_up, w_down, k=k, norm_topk_prob=True,
                           router_bias=p["bias"], held_from=first)
        return out, aux

    held = slice(first, first + count)
    out, vjp, aux = jax.vjp(layer, p["x"], p["w_gate"][held], p["w_up"][held], p["w_down"][held],
                            has_aux=True)
    return out, vjp(p["dout"]), aux


@pytest.mark.parametrize("tokens, favoured", [(128, None), (1024, 0)], ids=("every_share_whole_length", "a_share_on_each_side_of_the_bound"))
@pytest.mark.parametrize("held", [1, 2, 4])
def test_the_shares_add_up_to_the_whole_layer_and_their_gradients_are_its_slices(held, tokens, favoured):
    """Eight shares of one expert each (four of two, two of four): what every
    share computes alike (the router) counted once, their outputs add up to
    the uncut layer's, the matrices' gradients are the uncut gradient's
    slices, and the gradients for the tokens add up: their experts' part
    each, and the router's part, which every share computes whole, once.
    At 128 tokens the row bound of a share is every pair; at 1,024, with every
    token's first choice on expert 0, the share that holds it runs whole-length
    and the others over the prefix (`moe.held_row_bound`)."""
    import jax

    p = _expert_layer(tokens=tokens)
    if favoured is not None:
        p["bias"] = p["bias"].at[favoured].add(10.0)
    with jax.default_matmul_precision("highest"):
        whole_out, whole_grads, whole_aux = _share(p, 0, 8)
        shares = [_share(p, first, held) for first in range(0, 8, held)]
        # The uncut layer as the plain reference computes it: every expert on every token.
        scores = jax.nn.sigmoid(p["x"].reshape(-1, 32) @ p["router_w"])
        weights, _ = bench_lfm2.routing_matrix(scores, p["bias"], 2, True, 1.0)
        flat = p["x"].reshape(-1, 32)
        plain = sum(weights[:, e:e + 1] * ((jax.nn.silu(flat @ p["w_gate"][e]) * (flat @ p["w_up"][e]))
                                           @ p["w_down"][e]) for e in range(8))
    np.testing.assert_allclose(whole_out.reshape(-1, 32), plain, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(sum(out for out, _, _ in shares), whole_out, rtol=1e-4, atol=1e-5)
    for i, (_, grads, aux) in enumerate(shares):
        for mine, whole in zip(grads[1:], whole_grads[1:]):
            np.testing.assert_allclose(mine, whole[i * held:(i + 1) * held], rtol=1e-4, atol=1e-5)
        assert int(aux["held_pairs"]) == int(aux["rows_processed"])  # nothing dropped
        assert (aux["experts"] == whole_aux["experts"]).all()  # every share routes alike
    assert sum(int(aux["held_pairs"]) for _, _, aux in shares) == tokens * 2
    compact = [bool(aux["compact"]) for _, _, aux in shares]
    assert compact == [favoured is not None and held < 4 and i > 0 for i in range(8 // held)]
    np.testing.assert_allclose(sum(grads[0] for _, grads, _ in shares), whole_grads[0],
                               rtol=1e-4, atol=1e-5)


def test_a_share_that_holds_none_of_a_tokens_experts_adds_exactly_nothing():
    import jax.numpy as jnp

    p = _expert_layer()
    p["bias"] = jnp.zeros((8,)).at[jnp.array([6, 7])].set(10.0)  # every token picks 6 and 7
    out, grads, aux = _share(p, 0, 2)
    assert int(aux["held_pairs"]) == 0 and int(aux["rows_processed"]) == 0
    assert not np.asarray(out).any()
    assert all(not np.asarray(g).any() for g in grads)


# ------------------------------------------------------------ the patterned stack
def test_the_patterned_stack_is_its_layers_applied_one_by_one():
    """`apply_stack` over leading layers, a scan over periods and trailing
    layers against `block` on each layer of `Pattern.layers` in turn."""
    import dataclasses

    import jax

    from ray_tpu.models import LFM2Config, lfm2, stack
    from ray_tpu.models.llama import rope_tables

    types = (lfm2.CONV, lfm2.CONV) + (lfm2.ATTENTION, lfm2.CONV, lfm2.CONV, lfm2.CONV) * 2 + (
        lfm2.ATTENTION, lfm2.CONV)
    cfg = dataclasses.replace(LFM2Config.nano(dtype=jax.numpy.float32), layer_types=types)
    leading, period, n_periods, trailing = lfm2.layout(cfg)
    assert (len(leading), len(period), n_periods, len(trailing)) == (2, 4, 2, 2)
    assert lfm2.layout(LFM2Config())[1:] == (period, 9, trailing)  # the published forty: 2 + 9 x 4 + 2
    params = lfm2.init_params(cfg, jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, cfg.d_model))
    streams = rope_tables(32, cfg.head_dim, cfg.rope_theta)
    pattern = lfm2.pattern(cfg)
    assert len(pattern.layers(params["blocks"])) == cfg.n_layer == 12
    got, _ = stack.apply_stack(params["blocks"], x, cfg, pattern=pattern, attention_fn=None,
                               seq_streams=streams)
    want, kinds = x, []
    for kind, layer in pattern.layers(params["blocks"]):
        want, _ = stack.block(want, layer, cfg, *pattern.kinds[kind], streams=streams)
        kinds.append(kind)
    assert tuple(kinds) == lfm2.layer_kinds(cfg)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_conv_layers_mix_is_the_written_arithmetic_and_takes_the_kernels_gradient(monkeypatch, dtype):
    """`lfm2.short_conv` at a width the gradient kernel takes (128 channels: the nano model's 64 are no lane row),
    against the operator written out as the model had it until PR 62 (the shifts of z = b u): the value bit for
    bit, and with `gated_short_conv` told to take the kernel (interpret mode) the gradients to h and to every
    weight of the layer as jax's own of the written chain; on this platform the model itself asks for no kernel."""
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import LFM2Config, lfm2
    from ray_tpu.ops import short_conv as sc

    cfg = LFM2Config.nano(dtype=jnp.dtype(dtype))  # the operator reads the dtype alone: the widths are the operands'
    keys = jax.random.split(jax.random.PRNGKey(5), 5)
    layer = {"conv_in": jax.random.normal(keys[0], (128, 384)) * 0.1, "conv_w": jax.random.normal(keys[1], (3, 128)) * 0.5,
             "conv_out": jax.random.normal(keys[2], (128, 128)) * 0.1}
    h, weights = jax.random.normal(keys[3], (2, 48, 128)).astype(cfg.dtype), jax.random.normal(keys[4], (2, 48, 128))

    def written(h, layer):
        bcu = jnp.einsum("bsd,de->bse", h, layer["conv_in"].astype(cfg.dtype))
        b, c, u = (bcu[..., i * 128:(i + 1) * 128].astype(jnp.float32) for i in range(3))
        z, w = b * u, layer["conv_w"].astype(jnp.float32)
        late = lambda x, n: x if n == 0 else jnp.pad(x, ((0, 0), (n, 0), (0, 0)))[:, :x.shape[1]]  # noqa: E731
        y = (c * sum(w[j] * late(z, 2 - j) for j in range(3))).astype(cfg.dtype)
        return jnp.einsum("bsd,de->bse", y, layer["conv_out"].astype(cfg.dtype))

    def both(f):
        loss = lambda h, layer: (f(h, layer).astype(jnp.float32) * weights).sum()  # noqa: E731
        return jax.jit(lambda h, layer: (f(h, layer), jax.grad(loss, argnums=(0, 1))(h, layer)))(h, layer)

    model = lambda h, layer: lfm2.short_conv(h, layer, cfg)  # noqa: E731
    assert "pallas_call" not in str(jax.make_jaxpr(jax.grad(lambda h: model(h, layer).astype(jnp.float32).sum()))(h))
    want, want_grads = both(written)
    monkeypatch.setattr(lfm2, "gated_short_conv", functools.partial(sc.gated_short_conv, backend="pallas", interpret=True))
    assert "gated_conv_bwd" in str(jax.make_jaxpr(jax.grad(lambda h: model(h, layer).astype(jnp.float32).sum()))(h))
    got, grads = both(model)
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    far = lambda a, b: float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max() / jnp.abs(b.astype(jnp.float32)).max())  # noqa: E731
    worst = max(jax.tree.leaves(jax.tree.map(far, grads, want_grads)))
    assert worst < (5e-6 if dtype == "float32" else 2 ** -6), jax.tree.map(far, grads, want_grads)


def test_a_patterned_stack_under_a_pipeline_axis_says_so():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import LFM2Config, lfm2
    from ray_tpu.parallel import MeshSpec

    cfg = LFM2Config.nano()
    mesh = MeshSpec(pipeline=2).build(jax.devices()[:2])
    params = jax.eval_shape(lambda: lfm2.init_params(cfg, jax.random.PRNGKey(0)))
    with pytest.raises(NotImplementedError, match="pipeline"):
        jax.eval_shape(lambda p: lfm2.forward(p, jnp.zeros((2, 16), jnp.int32), cfg, mesh=mesh), params)


def test_the_initialised_tree_has_the_counted_parameters_and_its_axes():
    import jax

    from ray_tpu.models import LFM2Config, lfm2

    for cfg in (LFM2Config.nano(), LFM2Config.nano(n_experts_held=None)):
        shapes = jax.eval_shape(lambda: lfm2.init_params(cfg, jax.random.PRNGKey(0)))
        assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)) == lfm2.num_params(cfg)
        axes = lfm2.param_logical_axes(cfg)
        is_axes = lambda x: isinstance(x, tuple) and all(a is None or isinstance(a, str) for a in x)
        assert jax.tree.structure(shapes) == jax.tree.structure(axes, is_leaf=is_axes)
        assert all(len(a) == len(s.shape) for a, s in zip(
            jax.tree.leaves(axes, is_leaf=is_axes), jax.tree.leaves(shapes)))
        moe = axes["blocks"]["period"][1]["moe"]  # the period's second place: a conv layer
        assert moe["w_gate"] == ("layers", "expert", "embed", "mlp")
        assert len(axes["blocks"]["period"]) == 4  # a stack over the two periods for each place
        assert shapes["blocks"]["period"][1]["moe"]["router_w"].shape == (2, 64, 8)
    # The published model, whole: 24 B parameters, of which a token meets 2.3 B.
    full = LFM2Config()
    assert 23.8e9 < lfm2.num_params(full) < 23.9e9
    assert 2.2e9 < (lfm2.train_flops_per_token(full, 0) / 6) < 2.4e9
