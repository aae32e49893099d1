"""`models/granite_hybrid.py` (granite-4.0-h: Mamba-2 mixers round a NoPE GQA layer, four muP multipliers, a tied table)
against the float32 reference of `benchmark/models/granite_hybrid.py` at the nano size, on perturbed seeded weights (a
bias, a `D` and norm scales that are not their initial 0 and 1): loss and every leaf's gradient; each of the four
multipliers, the convolution's bias, `D` and the gate-before-norm order told apart (dropped from the program alone,
the comparison fails); the parameter count by hand; the step through `create_train_state` / `make_train_step`; the
state `check` reads; and `check` itself at the rehearsal's size."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.harness.manifest import Manifest  # noqa: E402
from benchmark.models import granite_hybrid as bench  # noqa: E402
from ray_tpu.models import create_train_state, default_optimizer, make_train_step  # noqa: E402
from ray_tpu.models import granite_hybrid as program  # noqa: E402

NANO = "granite-hybrid-nano"
LIMIT = 2e-4  # the float32 program from the float32 reference: loss (absolute) and a leaf's gradient (relative)


@pytest.fixture(scope="module")
def nano():
    return Manifest().config(NANO)


@pytest.fixture(scope="module")
def f32(nano):
    """(the configuration's dict, the program's configuration in float32 on the XLA forms, perturbed parameters,
    tokens, the reference's loss and gradients)."""
    c = {**nano, "dtype": "float32"}
    cfg = dataclasses.replace(bench.granite_hybrid_config(c), attention="xla")
    params = program.init_params(cfg, jax.random.PRNGKey(0))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = jax.tree.unflatten(tree, [p + 0.1 * jax.random.normal(k, p.shape) * (1.0 if p.ndim < 3 else 0.2)
                                       for p, k in zip(leaves, keys)])
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 49), 0, c["vocab_size"])  # 48 positions: three chunks of 16
    reference = jax.jit(jax.value_and_grad(lambda p: bench.reference_loss(p, tokens, c), has_aux=True))(params)
    return c, cfg, params, tokens, reference


def compare(cfg, params, tokens, reference):
    """(the loss's distance, the largest relative distance of a leaf's gradient and that leaf's path)."""
    (ref_loss, _), ref_grads = reference
    loss, grads = jax.jit(jax.value_and_grad(lambda p: program.loss_fn(p, {"tokens": tokens}, cfg)))(params)
    norm = lambda x: float(jnp.sqrt(jnp.sum(jnp.square(x))))  # noqa: E731
    far = jax.tree_util.tree_map_with_path(
        lambda path, a, b: (norm(a - b) / max(norm(b), 1e-30), jax.tree_util.keystr(path)), grads, ref_grads)
    return abs(float(loss) - float(ref_loss)), max(jax.tree.leaves(far, is_leaf=lambda x: isinstance(x, tuple)))


def test_the_model_agrees_with_the_reference_in_float32(f32):
    c, cfg, params, tokens, reference = f32
    assert cfg.layer_types == ("mamba", "attention", "mamba") and cfg.period == cfg.layer_types
    assert (cfg.embedding_multiplier, cfg.attention_multiplier, cfg.residual_multiplier, cfg.logits_scaling) == (
        12.0, 1 / 16, 0.22, 8.0)
    loss_err, (leaf_err, leaf) = compare(cfg, params, tokens, reference)
    assert loss_err < LIMIT and leaf_err < LIMIT, (loss_err, leaf_err, leaf)
    stats = reference[0][1]
    assert float(stats["decay_log_min"]) < 0 and stats["state"].shape == (2, 4, 16, 16)
    # the state `check` holds beside the reference's: the forward kernel's own hand-over behind the last chunk
    mine = program.first_state(params, tokens[:, :-1], cfg)
    assert float(jnp.linalg.norm(mine - stats["state"]) / jnp.linalg.norm(stats["state"])) < LIMIT


@pytest.mark.parametrize("field", ["embedding_multiplier", "attention_multiplier", "residual_multiplier", "logits_scaling"])
def test_a_multiplier_set_to_one_in_the_program_alone_is_told(f32, field):
    c, cfg, params, tokens, reference = f32
    loss_err, (leaf_err, _) = compare(dataclasses.replace(cfg, **{field: 1.0}), params, tokens, reference)
    assert max(loss_err, leaf_err) > 50 * LIMIT


@pytest.mark.parametrize("what", ["conv_bias", "D", "gate_behind_the_norm"])
def test_a_part_dropped_from_the_program_alone_is_told(f32, what, monkeypatch):
    """The convolution's bias and the skip `D x` left out, and the gate applied behind the norm instead of before it."""
    c, cfg, params, tokens, reference = f32
    if what == "conv_bias":
        real = program.short_conv
        monkeypatch.setattr(program, "short_conv", lambda z, taps, heads, bias, mesh: real(z, taps, heads, mesh=mesh))
    elif what == "D":
        real = program.ssd.ssd
        monkeypatch.setattr(program.ssd, "ssd", lambda x, b, c, dt, a_log, d, **kw: real(x, b, c, dt, a_log, 0 * d, **kw))
    else:
        def norm_then_gate(x, o, layer, config):
            b, h, s, p = o.shape
            z = program.rms_norm(x, layer["mixer_norm"], config.norm_eps) @ layer["w_z"]
            normed = program.rms_norm(o.transpose(0, 2, 1, 3).reshape(b, s, h * p), layer["gate_norm"], config.norm_eps)
            return (normed * jax.nn.silu(z)) @ layer["w_out"]

        monkeypatch.setattr(program, "mamba_out", norm_then_gate)
    loss_err, (leaf_err, _) = compare(cfg, params, tokens, reference)
    assert max(loss_err, leaf_err) > 50 * LIMIT


def test_the_parameter_count_by_hand():
    """ISSUE 73's count of the cell's cut: one period of ten layers over an eighth of the vocabulary."""
    cfg = program.GraniteHybridConfig(layer_types=program.PERIOD, vocab_size=12544)
    mamba = 2048 * 8512 + 4096 * 2048 + 5 * 4352 + 3 * 64 + 4096 + 3 * 2048 * 8192 + 2 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 8192 + 2 * 2048
    assert (mamba, attention) == (76_182_976, 60_821_504)
    assert program.num_params(cfg) == 9 * mamba + attention + 12544 * 2048 + 2048 == 772_160_448
    shapes = jax.eval_shape(lambda: program.init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 772_160_448
    assert "head" not in shapes and shapes["embed"].shape == (12544, 2048)  # tied: the table counts once
    assert cfg.period == program.PERIOD and len(shapes["blocks"]["period"]) == 10
    assert program.GraniteHybridConfig().layer_types.index("attention") == 5 and cfg.conv_channels == 4352


def test_the_step_trains_through_the_zoos_factory(nano):
    """`create_train_state` / `make_train_step` as every model: bf16 activations, the loss falls on one batch."""
    cfg = bench.granite_hybrid_config(nano)
    optimizer = default_optimizer(learning_rate=3e-3)
    state = create_train_state(cfg, jax.random.PRNGKey(0), optimizer)
    assert not state.compute  # no kernel's grouped operand
    step = make_train_step(cfg, optimizer)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(3), (2, 65), 0, nano["vocab_size"])}
    losses = []
    for _ in range(4):
        state, out = step(state, batch)
        losses.append(float(out["loss"]))
    assert losses[0] == pytest.approx(jnp.log(256.0), abs=0.3) and losses[-1] < losses[0] - 0.02
    assert all(p.dtype == jnp.float32 for p in jax.tree.leaves(state.params))


def test_check_at_the_rehearsals_size(nano):
    """`check` as the cell's worker calls it: ok under the toy's limits, with the two counters (a multiplier left
    out comes out not ok there too: `tools/granite_hybrid_readings.py --config granite-hybrid-nano`, walked by hand)."""
    system = bench.build(nano, None, 0)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, 65), 0, nano["vocab_size"])
    out = bench.check(system, tokens)
    assert out["ok"], out
    assert out["ssd.decay_log_min"] < 0 and set(out["ssd.state_rms"]) == {"system", "reference"}
    assert out["state_rel_err"] < 0.02 and set(out["leaf_grad_rel_err"]) == set(bench.CHECKED_LEAVES)
    assert jax.tree.leaves(system.state.opt_state)  # the moments were set aside and made again
    assert system.attention_path(1, 4096, "tpu") == "pallas" and system.attention_path(1, 4096, "cpu") == "xla"
