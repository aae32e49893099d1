"""GLM-4 MoE Lite through `create_train_state` / `make_train_step` against the
plain float32 reference of `benchmark/models/glm4_moe_lite.py`, at nano size on
the CPU (one dense and two expert layers, hidden 64, latents of 32 and 16, 4
heads of 16 + 16, a router over 8 experts of which this share holds 2, 2 a
token, one shared expert, one prediction module, 64 positions); on the chip the
same comparison runs at the published widths.

Beside it: latent attention alone against the reference's; the share tied to
the model (the eight shares' routed parts add up to the whole layer's, the
shared expert counted once); the model without the module equal to the model
with `num_nextn_predict_layers` 0 to the bit; the selection bias and nothing
else frozen; and the negative cases that say what the comparison can see."""

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
from benchmark.models import glm4_moe_lite as bench  # noqa: E402

CONFIG, CELL = "glm-4.7-flash-ep8-l5", "glm-4.7-flash-ep8-l5.fed4k"


@pytest.fixture(scope="module")
def nano():
    return Manifest().config("glm4-moe-lite-nano")


@pytest.fixture(scope="module")
def tokens():
    import jax.numpy as jnp

    return jnp.asarray(np.random.default_rng(0).integers(0, 255, (2, 65), dtype=np.int32))


def _trained(c, tokens, steps=60):
    """Weights that mean something: at seeded initial weights the loss hardly
    depends on what attention and the experts do."""
    import jax

    system = bench.build(dict(c, learning_rate=3e-3), None, 7)
    # ... and a selection bias as large as a balancing rule would make it.
    system.state.params = jax.tree_util.tree_map_with_path(
        lambda path, p: p * 200.0 if "expert_bias" in jax.tree_util.keystr(path) else p,
        system.state.params)
    for _ in range(steps):
        system.state, metrics = system.step(system.state, {"tokens": tokens})
    assert float(metrics["loss"]) < 2.0
    return system


@pytest.fixture(scope="module")
def trained_f32(nano, tokens):
    return _trained(dict(nano, dtype="float32"), tokens)


# A bf16 system built once; `check` keeps the system's side of the comparison for it and for
# `trained_f32`, which the four negative cases read (`tests/shared_checks.py`).
@pytest.fixture(scope="module")
def bf16(nano):
    return bench.build(nano, None, 0)


@pytest.fixture(scope="module")
def check():
    return shared_checks.Checked(bench)


def _both_heads(system, tokens, params):
    """(main logits, module logits) of the program, as `loss_fn` computes them."""
    from ray_tpu.models import glm4_moe_lite as program

    cfg = system.cfg
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x = program.hidden(params, inputs, cfg)
    return program.forward(params, inputs, cfg), program.mtp_logits(params, x, targets, cfg)


def test_the_bf16_system_is_within_the_written_tolerance_of_the_reference(bf16, check, tokens):
    got = check(bf16, tokens)
    assert got["ok"], got
    assert got["routing"]["dropped"] == 0 and len(got["routing"]["held_pairs_per_layer"]) == 3
    assert got["loss_abs_err"] < bench.LOSS_ABS_TOL and got["grad_norm_rel_err"] < bench.GRAD_NORM_REL_TOL
    # Two heads: ln(256) at the first, 0.3 x ln(256) at the second.
    assert got["loss_reference"] == pytest.approx(1.3 * np.log(256), rel=0.02)


def test_in_float32_they_agree_to_rounding_by_leaf_logit_and_choice(trained_f32, tokens):
    import jax

    from ray_tpu.models import glm4_moe_lite as program

    system, c = trained_f32, trained_f32.c
    params = system.state.params
    loss, grads = jax.jit(jax.value_and_grad(lambda p: program.loss_fn(p, {"tokens": tokens}, system.cfg)))(params)
    (ref_loss, chosen), ref_grads = jax.jit(jax.value_and_grad(
        lambda p: bench.reference_loss(p, tokens, c), has_aux=True))(params)
    assert float(loss) == pytest.approx(float(ref_loss), abs=2e-5)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(ref_grads)):
        scale = float(np.abs(r).max()) + 1e-12
        assert float(np.abs(g - r).max()) <= 2e-3 * scale + 1e-6, jax.tree_util.keystr(path)
    # The selection bias enters the choice only: no gradient reaches it, in either.
    for tree in (grads, ref_grads):
        bias = tree["blocks"]["period"][0]["moe"]["expert_bias"]
        assert float(np.abs(bias).max()) == 0.0 and float(np.abs(tree["mtp"]["block"]["moe"]["expert_bias"]).max()) == 0.0
    main, module = jax.jit(lambda p: _both_heads(system, tokens, p))(params)
    _, _, ref_main, ref_module, _ = jax.jit(lambda p: bench.reference_logits(p, tokens, c))(params)
    assert float(np.abs(main - ref_main).max()) < 2e-4 * float(np.abs(ref_main).max())
    assert float(np.abs(module - ref_module).max()) < 2e-4 * float(np.abs(ref_module).max())
    stats = program.routing_stats(params, tokens, system.cfg)
    same = np.take_along_axis(np.asarray(chosen), np.asarray(stats["experts"]), axis=-1)
    assert same.all() and stats["experts"].shape == (3, 128, 2) and int(stats["dropped"].sum()) == 0


def test_they_agree_at_trained_weights_too(trained_f32, check, tokens):
    got = check(trained_f32, tokens, loss_tol=2e-5, grad_tol=2e-4, flipped_tol=0.0)
    assert got["ok"], got


def test_latent_attention_alone_is_the_references(nano):
    """Both down-projections, the two norms of the latents, both up-projections,
    the rotation of the 16 rotary dimensions and the one rotary key for every
    head: the program's `qkv_part` and the XLA attention against the
    reference's `latent_attention`, float32, scales that are not 1."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import glm4_moe_lite as program
    from ray_tpu.ops.flash_attention import xla_attention

    c = dict(nano, dtype="float32")
    cfg = bench.model_config(c)
    layer = jax.jit(lambda key: program.init_params(cfg, key))(jax.random.PRNGKey(3))["blocks"]["leading"][0]
    keys = jax.random.split(jax.random.PRNGKey(4), 4)
    layer = {**layer, **{name: 1.0 + 0.3 * jax.random.normal(k, layer[name].shape)
                         for name, k in zip(("attn_norm", "q_a_norm", "kv_a_norm"), keys)}}
    x = jax.random.normal(keys[3], (2, 64, cfg.d_model))
    cos, sin = program._streams(64, cfg)
    qkv_part, _ = program.pattern(cfg).kinds[program.DENSE]
    q, k, v = qkv_part(x, layer, cos, sin)
    assert q.shape == k.shape == v.shape == (2, 4, 64, 32)
    # One rotary key for every head: the last 16 of k are the same in all four.
    assert float(jnp.abs(k[:, :, :, 16:] - k[:, :1, :, 16:]).max()) == 0.0
    got = xla_attention(q, k, v).transpose(0, 2, 1, 3).reshape(2, 64, 4 * 32)
    full_cos, full_sin = jnp.concatenate([cos, cos], -1), jnp.concatenate([sin, sin], -1)
    with jax.default_matmul_precision("highest"):
        h = bench.rms_norm(x, layer["attn_norm"], c["rms_norm_eps"])
        want = bench.latent_attention(h, layer, c, full_cos, full_sin)
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(jnp.abs(want).max()) + 1e-6
    # Without the norm of the key/value latent it is another function.
    flat = {**layer, "kv_a_norm": jnp.ones_like(layer["kv_a_norm"])}
    with jax.default_matmul_precision("highest"):
        other = bench.latent_attention(h, flat, c, full_cos, full_sin)
    assert float(jnp.abs(got - other).max()) > 1e-3 * float(jnp.abs(want).max())


def test_no_optimizer_step_moves_the_selection_bias_and_every_other_leaf_moves(nano, tokens):
    import jax

    from ray_tpu.models import glm4_moe_lite as program

    system = bench.build(dict(nano, learning_rate=1e-2), None, 0)
    frozen = program.frozen_params(system.cfg)
    before = jax.tree.map(np.asarray, system.state.params)
    for _ in range(3):
        system.state, _ = system.step(system.state, {"tokens": tokens})
    moved = jax.tree.map(lambda a, b: bool(np.any(np.asarray(a) != b)), system.state.params, before)
    named = {jax.tree_util.keystr(p): (m, f) for (p, m), f in zip(
        jax.tree_util.tree_leaves_with_path(moved), jax.tree.leaves(frozen))}
    assert {k for k, (m, f) in named.items() if f} == {k for k in named if "expert_bias" in k}
    assert len([k for k in named if "expert_bias" in k]) == 2  # the stack's, the module's
    assert all(m != f for m, f in named.values()), {k: v for k, v in named.items() if v[0] == v[1]}


def test_without_the_module_it_is_the_model_with_no_module_to_the_bit(nano, tokens):
    """`num_nextn_predict_layers` 0: no `mtp` leaves, and loss and gradients
    equal, bit for bit, the main head's cross entropy of the model that has
    the module (the module reads the stack and writes nothing back)."""
    import jax

    from ray_tpu.models import glm4_moe_lite as program
    from ray_tpu.models.stack import causal_lm_loss

    with_module = bench.model_config(dict(nano, dtype="float32"))
    without = bench.model_config(dict(nano, dtype="float32", num_nextn_predict_layers=0))
    params = jax.jit(lambda key: program.init_params(with_module, key))(jax.random.PRNGKey(0))
    bare = jax.jit(lambda key: program.init_params(without, key))(jax.random.PRNGKey(0))
    assert "mtp" not in bare and set(params) - set(bare) == {"mtp"}
    assert all(bool((a == b).all()) for a, b in zip(
        jax.tree.leaves(bare), jax.tree.leaves({k: v for k, v in params.items() if k != "mtp"})))
    assert program.num_params(with_module) - program.num_params(without) == sum(
        x.size for x in jax.tree.leaves(params["mtp"]))

    def main_head_alone(p):
        return causal_lm_loss(program.forward(p, tokens[:, :-1], with_module), tokens[:, 1:])

    loss, grads = jax.value_and_grad(lambda p: program.loss_fn(p, {"tokens": tokens}, without))(bare)
    want, want_grads = jax.value_and_grad(main_head_alone)(params)
    assert float(loss) == float(want)
    want_grads.pop("mtp")
    assert all(bool((a == b).all()) for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)))
    # ... and with the module the loss is that plus 0.3 times the second cross entropy.
    both = float(program.loss_fn(params, {"tokens": tokens}, with_module))
    _, aux = program.forward(params, tokens[:, :-1], with_module, return_aux=True, targets=tokens[:, 1:])
    assert both == pytest.approx(float(want) + float(aux), abs=1e-6) and float(aux) > 0.25 * float(want)


def test_lm_loss_hands_targets_to_a_model_with_prediction_depths_alone(tokens):
    """Every other model's `forward` is called as it always was."""
    import types

    from ray_tpu.models.stack import lm_loss

    seen = []

    def forward(params, inputs, config, attention_fn, dropout_rng, mesh, num_microbatches, **kw):
        import jax.numpy as jnp

        seen.append(sorted(kw))
        return jnp.zeros(inputs.shape + (8,)), None

    lm_loss(forward, None, {"tokens": tokens % 8}, types.SimpleNamespace())
    lm_loss(forward, None, {"tokens": tokens % 8}, types.SimpleNamespace(n_predict_layers=0))
    lm_loss(forward, None, {"tokens": tokens % 8}, types.SimpleNamespace(n_predict_layers=1))
    assert seen == [["return_aux"], ["return_aux"], ["return_aux", "targets"]]


def test_a_masked_mean_is_the_mean_over_what_the_mask_keeps():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.stack import causal_lm_loss

    logits = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16))
    targets = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 16)
    assert float(causal_lm_loss(logits, targets, mask=jnp.arange(8) < 7)) == pytest.approx(
        float(causal_lm_loss(logits[:, :7], targets[:, :7])), rel=1e-6)
    assert float(causal_lm_loss(logits, targets, mask=jnp.ones((2, 8), bool))) == pytest.approx(
        float(causal_lm_loss(logits, targets)), rel=1e-6)


# ------------------------------------------------------ what the comparison can see
def _hidden_first_swapped(real):
    """The module's concatenation in the other order."""
    def reference_logits(params, tokens, c, dtype=None):
        import jax.numpy as jnp

        d = c["hidden_size"]
        eh = params["mtp"]["eh_proj"]
        swapped = {**params, "mtp": {**params["mtp"], "eh_proj": jnp.concatenate([eh[d:], eh[:d]])}}
        return real(swapped, tokens, c, dtype)
    return reference_logits


def _no_shared_expert(real):
    def reference_logits(params, tokens, c, dtype=None):
        import jax

        zeroed = jax.tree_util.tree_map_with_path(
            lambda path, p: p * 0 if "shared_down" in jax.tree_util.keystr(path) else p, params)
        return real(zeroed, tokens, c, dtype)
    return reference_logits


def _unscaled(real):
    return lambda params, tokens, c, dtype=None: real(params, tokens, {**c, "routed_scaling_factor": 1.0}, dtype)


def _module_unweighted(real):
    return lambda params, tokens, c, dtype=None: real(params, tokens, {**c, "mtp_loss_weight": 1.0}, dtype)


@pytest.mark.parametrize("name,wrong,of", [
    ("the_modules_halves_swapped", _hidden_first_swapped, "reference_logits"),
    ("no_shared_expert", _no_shared_expert, "reference_logits"),
    ("weights_not_scaled_by_1.8", _unscaled, "reference_logits"),
    ("the_second_loss_at_weight_1", _module_unweighted, "reference_loss"),
])
def test_a_reference_of_another_function_fails_the_comparison(trained_f32, check, tokens, monkeypatch, name, wrong, of):
    monkeypatch.setattr(bench, of, wrong(getattr(bench, of)))
    got = check(trained_f32, tokens)
    assert not got["ok"], (name, got)
    assert got["loss_abs_err"] > bench.LOSS_ABS_TOL or got["grad_norm_rel_err"] > bench.GRAD_NORM_REL_TOL


def test_the_reference_in_bf16_is_outside_a_tolerance(nano, tokens):
    """The nearest precision below the configuration's, parameters, router,
    norms and logits included; the cross entropy of those logits is summed in
    float32, as a program in that precision would. So its loss lands inside
    the loss bound (1.5e-5 to 1.4e-4 off over six seeds here, 1.3e-4 to 9.1e-4
    on the chip: PERF.md section 6, PR 39) and the loss cannot tell; its router
    picks other experts for more (token, slot) choices than the bound on
    flipped choices allows (2.0 to 3.4 % here, 1.75 to 1.95 % on the chip)."""
    import jax
    import jax.numpy as jnp

    params = bench.build(nano, None, 2).state.params
    exact, chosen = jax.jit(lambda p: bench.reference_loss(p, tokens, nano))(params)
    rounded, low_chosen = jax.jit(lambda p: bench.reference_loss(p, tokens, nano, dtype=jnp.bfloat16))(params)
    low_experts = jax.lax.top_k(low_chosen.astype(jnp.float32), nano["num_experts_per_tok"])[1]
    flipped = 1.0 - float(jnp.take_along_axis(chosen, low_experts, axis=-1).mean())
    assert flipped > bench.FLIPPED_SHARE_TOL
    assert 0 < abs(float(exact) - float(rounded)) < bench.LOSS_ABS_TOL


def test_parameters_kept_in_bf16_fail_the_check(bf16, tokens):
    import jax.numpy as jnp

    got = bench.check(shared_checks.in_dtype(bf16, jnp.bfloat16), tokens)
    assert not got["ok"] and got["state_dtypes_other_than_stated"] == ["bfloat16"]


def test_a_dropped_shared_expert_fails_the_check(nano, tokens, monkeypatch):
    """What the tolerances are tight enough to see at nano size, with a shared
    expert that weighs what a trained one does (the whole reference in bf16:
    `tests/test_glm4_moe_lite.py`)."""
    import jax

    system = bench.build(dict(nano, dtype="float32"), None, 0)
    real = bench.reference_logits
    system.state.params = jax.tree_util.tree_map_with_path(
        lambda path, p: p * 30.0 if "shared_" in jax.tree_util.keystr(path) else p, system.state.params)
    check = shared_checks.Checked(bench)  # the system's side once for the two comparisons: its weights stay
    assert check(system, tokens)["ok"]

    def no_shared(params, tokens, c, dtype=None):
        return real(jax.tree_util.tree_map_with_path(
            lambda path, p: p * 0 if "shared_down" in jax.tree_util.keystr(path) else p, params), tokens, c, dtype)

    monkeypatch.setattr(bench, "reference_logits", no_shared)
    assert not check(system, tokens)["ok"]


# ------------------------------------------------------------------ the share
def _expert_layer(seed=0, tokens=256, d=32, f=16, n_experts=8):
    import jax

    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    normal = lambda k, *shape: 0.3 * jax.random.normal(k, shape)  # noqa: E731
    return {
        "x": jax.random.normal(ks[0], (2, tokens // 2, d)), "router_w": normal(ks[1], d, n_experts),
        "bias": 0.5 * jax.random.normal(ks[2], (n_experts,)),
        "w_gate": normal(ks[3], n_experts, d, f), "w_up": normal(ks[4], n_experts, d, f),
        "w_down": normal(ks[5], n_experts, f, d),
        "shared": (normal(ks[6], d, f), normal(ks[7], d, f), normal(ks[8], f, d)),
    }


@pytest.mark.parametrize("held", [1, 2, 4])
def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_whole_layer(held):
    """`model-configs` section 4: a chip that holds `held` of 8 routed experts
    returns its experts' partial sum; every chip adds the shared expert whole
    for its own tokens. The 8 / held shares' routed parts, added, plus the
    shared expert once, are the uncut layer: routed by all 8 experts plus the
    shared one, as the reference computes it."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.moe import moe_mlp, shared_expert

    p = _expert_layer()
    kw = dict(k=2, norm_topk_prob=True, router_bias=p["bias"], weight_scale=1.8)

    def routed(first, count):
        part = slice(first, first + count)
        return moe_mlp(p["x"], p["router_w"], p["w_gate"][part], p["w_up"][part], p["w_down"][part],
                       held_from=first, **kw)[0]

    shared = shared_expert(p["x"], *p["shared"])
    whole = routed(0, 8) + shared
    added = sum(routed(first, held) for first in range(0, 8, held)) + shared
    assert float(jnp.abs(added - whole).max()) < 1e-5 * float(jnp.abs(whole).max())
    # Counted once a share, the shared expert would stand in the sum 8 / held times.
    per_share = sum(routed(first, held) + shared for first in range(0, 8, held))
    if held < 8:
        assert float(jnp.abs(per_share - whole).max()) > 0.1 * float(jnp.abs(shared).max())
    # The uncut layer is the reference's arithmetic: a dense routing matrix, every expert on every token.
    x = p["x"].reshape(-1, 32)
    with jax.default_matmul_precision("highest"):
        weights, _ = bench.routing_matrix(jax.nn.sigmoid(x @ p["router_w"]), p["bias"], 2, True, 1.8)
        swiglu = lambda w1, w3, w2: (jax.nn.silu(x @ w1) * (x @ w3)) @ w2  # noqa: E731
        want = sum(weights[:, e, None] * swiglu(p["w_gate"][e], p["w_up"][e], p["w_down"][e]) for e in range(8))
        want = want + swiglu(*p["shared"])
    assert float(jnp.abs(whole.reshape(-1, 32) - want).max()) < 2e-5 * float(jnp.abs(want).max())


def test_the_shared_expert_takes_gradients_to_all_three_matrices_and_its_input():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.moe import shared_expert

    p = _expert_layer()
    grads = jax.grad(lambda x, ws: shared_expert(x, *ws).sum(), argnums=(0, 1))(p["x"], p["shared"])
    x = p["x"].reshape(-1, 32)
    want = jax.grad(lambda x, ws: ((jax.nn.silu(x @ ws[0]) * (x @ ws[1])) @ ws[2]).sum(), argnums=(0, 1))(
        x, p["shared"])
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        assert float(jnp.abs(g.reshape(w.shape) - w).max()) < 1e-5 * float(jnp.abs(w).max())


# ------------------------------------------------------------------ the tree
def test_the_initialised_tree_has_the_counted_parameters_and_its_axes():
    import jax

    from ray_tpu.models import GLM4MoELiteConfig
    from ray_tpu.models import glm4_moe_lite as program
    from ray_tpu.models.training import model_for

    cfg = GLM4MoELiteConfig.nano()
    assert model_for(cfg) is program
    params = program.init_params(cfg, jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(params)) == program.num_params(cfg)
    axes = program.param_logical_axes(cfg)
    is_axes = lambda x: isinstance(x, tuple) and all(a is None or isinstance(a, str) for a in x)  # noqa: E731
    flat_axes = jax.tree.leaves(axes, is_leaf=is_axes)
    assert jax.tree.structure(axes, is_leaf=is_axes) == jax.tree.structure(params)
    assert all(len(a) == p.ndim for a, p in zip(flat_axes, jax.tree.leaves(params)))
    stack = params["blocks"]["period"][0]
    assert stack["wkv_b"].shape == (2, 16, 4, 16 + 32) and stack["moe"]["w_gate"].shape == (2, 2, 64, 32)
    assert axes["blocks"]["period"][0]["moe"]["w_gate"] == ("layers", "expert", "embed", "mlp")
    assert axes["mtp"]["block"]["wq_b"] == (None, "heads", None)
    # The published sizes, this chip's share: 706 M parameters (ISSUE 39's arithmetic).
    share = GLM4MoELiteConfig(n_layer=5, n_experts_held=8, vocab_size=19360)
    assert program.num_params(share) == 706_518_848
    assert program.layer_kinds(share) == ("latent_dense",) + ("latent_moe",) * 4


def test_the_stack_is_its_layers_applied_one_by_one(nano, tokens):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import glm4_moe_lite as program
    from ray_tpu.models.stack import block

    cfg = bench.model_config(dict(nano, dtype="float32"))
    params = program.init_params(cfg, jax.random.PRNGKey(1))
    inputs = tokens[:, :-1]
    walked = program.pattern(cfg)
    x = params["embed"][inputs]
    for kind, layer in walked.layers(params["blocks"]):
        x, _ = block(x, layer, cfg, *walked.kinds[kind], streams=program._streams(64, cfg))
    assert float(jnp.abs(x - program.hidden(params, inputs, cfg)).max()) < 1e-5


# ------------------------------------------------------------------ the cell's rehearsal
def test_the_cells_cpu_rehearsal_prints_the_contracts_line():
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "2147493039",
         "--seconds", "2", "--trace", "1", "--rehearse-cpu"],
        cwd=REPO, capture_output=True, text=True, timeout=400)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 3
    assert line["device"]["platform"] == "cpu" and "platform=cpu" in proc.stdout
    assert all(name.startswith("rehearsal.") for name in line["metrics"])
    assert f"rehearsal.data.wait_ms.{CONFIG}" in line["metrics"]
    assert 0.0 < line["metrics"][f"rehearsal.moe.held_pairs_share.{CONFIG}"]["value"] < 0.6
    assert line["metrics"][f"rehearsal.moe.issued_over_held.{CONFIG}"]["value"] >= 1.0
    assert '"dropped": 0' in proc.stdout and "expert_choices_flipped_share" in proc.stdout
    # Three expert layers walked: the stack's two and the prediction module's.
    check = json.loads(proc.stdout.split("[run] check ", 1)[1].splitlines()[0])
    assert len(check["routing"]["held_pairs_per_layer"]) == 3
