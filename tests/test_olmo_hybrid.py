"""`models/olmo_hybrid.py` at the nano size on the CPU: the model against `benchmark/models/olmo_hybrid.py
reference_loss` through the cell's own `check` (loss, gradient norm, the three leaves that only the scan's
backward pass reaches), what `check` says of a state or a decay kept in bf16 and of a decay left out, the step
under an 8-device CPU mesh `fsdp=4` against the unsharded step, and `stack.Pattern` with a kind's own `attend`
beside a plain kind over two periods."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import shared_checks  # noqa: E402
from benchmark.harness.manifest import Manifest  # noqa: E402
from benchmark.models import olmo_hybrid as bench  # noqa: E402
from ray_tpu.models import olmo_hybrid as program  # noqa: E402
from ray_tpu.models import stack  # noqa: E402
from ray_tpu.ops import gated_delta_rule as gdn  # noqa: E402

# In float32 the toy agrees with the reference to rounding (1e-7 in the loss, 1e-6 to 7e-5 elsewhere over three
# seeds); in bf16 its 64-term sums read percents (the configuration's `check_tolerances` says why).
F32_LIMITS = dict(loss_tol=1e-5, grad_tol=5e-4, leaf_tol=1e-3)


@pytest.fixture(scope="module")
def nano():
    return Manifest().config("olmo-hybrid-nano")


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(0).integers(0, 255, (2, 41), dtype=np.int32))  # 40 positions: no whole chunk


@pytest.fixture(scope="module")
def f32(nano):
    return bench.build({**nano, "dtype": "float32"}, None, 3)


@pytest.fixture(scope="module")
def check():
    return shared_checks.Checked(bench)


def test_the_configuration_is_the_published_one_but_for_its_depth():
    cfg = bench.olmo_hybrid_config(Manifest().config("olmo-hybrid-7b-fsdp4"))
    full = program.OlmoHybridConfig()
    assert cfg == program.OlmoHybridConfig(layer_types=program.PERIOD * 2)
    assert (full.n_layer, cfg.n_layer, cfg.period) == (32, 8, program.PERIOD)
    assert (cfg.d_model, cfg.head_dim, cfg.linear_key_dim, cfg.linear_value_dim, cfg.d_ff) == (3840, 128, 96, 192, 11008)
    # ISSUE 51's arithmetic: a linear layer 215.6 M, a full one 185.8 M, a period 832.5 M, two and the tables 2,436 M.
    kinds = {kind: sum(program._kind_params(cfg, kind).values()) for kind in (program.LINEAR, program.FULL)}
    assert (round(kinds[program.LINEAR] / 1e5), round(kinds[program.FULL] / 1e5)) == (2156, 1858)
    assert program.num_params(cfg) == 2_435_748_072
    assert bench.matmul_params(Manifest().config("olmo-hybrid-7b-fsdp4")) == sum(
        program._kind_params(cfg, kind)["matmul"] for kind in cfg.layer_types) + cfg.vocab_size * cfg.d_model


def test_the_parameters_are_counted_laid_out_and_started_as_said(f32):
    cfg, params = f32.cfg, f32.state.params
    assert program.num_params(cfg) == sum(x.size for x in jax.tree.leaves(params))
    axes = program.param_logical_axes(cfg)
    is_axes = lambda x: isinstance(x, tuple) and all(a is None or isinstance(a, str) for a in x)  # noqa: E731
    assert jax.tree.structure(axes, is_leaf=is_axes) == jax.tree.structure(params)
    for leaf, names in zip(jax.tree.leaves(params), jax.tree.leaves(axes, is_leaf=is_axes)):
        assert leaf.ndim == len(names)
        if leaf.ndim >= 2 and min(leaf.shape[-2:]) >= cfg.linear_heads and leaf.shape[-2] != cfg.conv_kernel:
            assert "embed" in names  # every matrix shards over its hidden axis, as GPT-2's
    first = params["blocks"]["period"][0]
    assert first["A_log"].shape == (2, cfg.linear_heads) and len(params["blocks"]["period"]) == 4
    a, dt = jnp.exp(first["A_log"]), jax.nn.softplus(first["dt_bias"])
    assert 0 < float(a.min()) and float(a.max()) <= 16 and 1e-3 <= float(dt.min()) and float(dt.max()) <= 0.1 + 1e-6


def test_the_model_agrees_with_the_reference_in_float32(f32, tokens, check):
    got = check(f32, tokens, **F32_LIMITS)
    assert got["ok"], got
    assert got["loss_abs_err"] < 1e-5 and got["grad_norm_rel_err"] < 5e-4
    assert set(got["leaf_grad_norm_rel_err"]) == {"wk", "w_a", "A_log"}
    assert min(got["leaf_grad_norm_reference"].values()) > 0  # the scan's backward pass reaches all three
    assert 0.2 < got["gdn.neg_eigval_share"] < 0.8 and 0 < got["gdn.decay_min"] < 1
    assert not got["state_dtypes_other_than_stated"]


FAULT_ROWS = np.random.default_rng(1).integers(0, 255, (2, 65), dtype=np.int32)  # 64 positions: four chunks of 16, a state to carry


def _system_side(system, monkeypatch):
    """`check`'s program of the system (loss, gradient norm, the three leaves) with the scan in chunks of 16."""
    monkeypatch.setattr(gdn.gated_delta_rule, "__kwdefaults__", {**gdn.gated_delta_rule.__kwdefaults__, "chunk": 16})
    of_system, _ = bench.losses_and_norms(system)
    loss, norm, leaves = jax.device_get(jax.jit(of_system)(system.state.params, jnp.asarray(FAULT_ROWS)))
    return float(loss), float(norm), np.asarray(leaves)


@pytest.fixture(scope="module")
def sound(f32):
    with pytest.MonkeyPatch.context() as patch:
        return _system_side(f32, patch)


@pytest.mark.parametrize("fault", ("state_in_bf16", "decay_in_bf16", "decay_dropped"))
def test_a_state_or_a_decay_in_bf16_or_a_decay_left_out_passes_the_limits_of_the_float32_comparison(
        f32, sound, fault, monkeypatch):
    """The program of the system that `check` compares, with the fault planted in the chunk's mathematics,
    against the same program sound (which the test above holds to the reference): each fault moves a named
    leaf, whose gradient only the scan's backward pass makes, past the limit that test passes under. So a
    scan that kept its state or its decay in bf16 fails that test."""
    forward, gates = gdn._chunk_fwd, gdn._chunk_gates
    bf16 = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    if fault == "state_in_bf16":
        monkeypatch.setattr(gdn, "_chunk_fwd", lambda q, k, v, gam, beta, s: forward(q, k, v, gam, beta, bf16(s)))
    elif fault == "decay_in_bf16":
        monkeypatch.setattr(gdn, "_chunk_gates", lambda k, gam, beta: gates(k, bf16(gam), beta))
    else:
        monkeypatch.setattr(gdn, "_chunk_gates", lambda k, gam, beta: gates(k, jnp.zeros_like(gam), beta))
    _, _, leaves = _system_side(f32, monkeypatch)
    moved = np.abs(leaves - sound[2]) / sound[2]
    assert moved.max() > F32_LIMITS["leaf_tol"], dict(zip(bench.CHECKED_LEAVES, moved))


def test_check_sets_the_fresh_moments_aside_and_makes_them_again(f32, tokens):
    before = f32.state.opt_state
    with bench._moments_set_aside(f32):
        assert f32.state.opt_state is None
    after = f32.state.opt_state
    assert jax.tree.structure(after) == jax.tree.structure(before)
    assert all(a.shape == b.shape and a.dtype == b.dtype and not np.any(np.asarray(a))
               for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(before)) if a.ndim)
    stepped = shared_checks.copy.copy(f32)
    stepped.state = shared_checks.dataclasses.replace(f32.state, step=jnp.ones((), jnp.int32))
    with bench._moments_set_aside(stepped):
        assert stepped.state.opt_state is not None  # a state that has taken a step keeps its moments


def test_the_step_under_fsdp_4_is_the_unsharded_step(nano):
    from ray_tpu.models import create_train_state, default_optimizer, make_train_step, shard_batch
    from ray_tpu.parallel import MeshSpec

    cfg = bench.olmo_hybrid_config({**nano, "dtype": "float32"})
    opt = default_optimizer(learning_rate=1e-3)
    rows = np.random.default_rng(2).integers(0, 255, (4, 49), dtype=np.int32)
    alone = create_train_state(cfg, jax.random.PRNGKey(0), opt)
    _, want = make_train_step(cfg, opt, donate=False)(alone, {"tokens": jnp.asarray(rows)})
    mesh = MeshSpec(fsdp=4).build(jax.devices()[:4])
    sharded = create_train_state(cfg, jax.random.PRNGKey(0), opt, mesh=mesh)
    wq = sharded.params["blocks"]["period"][0]["wq"]
    assert wq.sharding.shard_shape(wq.shape) == (2, cfg.d_model // 4, cfg.linear_heads * cfg.linear_key_dim)
    mu = jax.tree.leaves(sharded.opt_state, is_leaf=lambda x: hasattr(x, "sharding") and x.shape == wq.shape)
    assert all(m.sharding == wq.sharding for m in mu if getattr(m, "shape", None) == wq.shape)
    new, got = make_train_step(cfg, opt, mesh=mesh, donate=False)(sharded, shard_batch({"tokens": rows}, mesh))
    assert float(got["loss"]) == pytest.approx(float(want["loss"]), rel=1e-5)
    assert float(got["grad_norm"]) == pytest.approx(float(want["grad_norm"]), rel=1e-4)
    assert int(new.step) == 1


def test_forward_gives_logits_over_the_whole_untied_vocabulary(f32, tokens):
    logits = jax.jit(lambda p: program.forward(p, tokens[:, :-1], f32.cfg))(f32.state.params)
    assert logits.shape == (2, 40, 256) and logits.dtype == jnp.float32
    assert f32.state.params["head"] is not f32.state.params["embed"]
    assert program.train_flops_per_token(f32.cfg, 64) > 6 * 256 * 64


# ------------------------------------------------------------------ stack.Pattern, a kind with its own `attend`
def test_a_pattern_runs_a_kinds_own_attend_beside_a_plain_kind_over_two_periods():
    """Two periods of (own, plain, own): the kind with an `attend` of its own gets its fourth part and never
    the attention dispatch; the plain kind gets the dispatch; under `save_attn` neither middle is recomputed."""
    import dataclasses

    @dataclasses.dataclass(frozen=True)
    class Config:
        n_layer: int = 6
        remat: bool = True
        remat_policy: str = "save_attn"
        attention: str = "xla"

    calls = {"own": 0, "dispatch": 0}

    def own_qkv(x, layer):
        h = x * layer["w"]
        return h[:, None], h[:, None], h[:, None], layer["w"] * 2.0

    def own_attend(q, k, v, doubled, attention_fn, mesh):
        calls["own"] += 1
        return (jnp.cumsum(v, axis=2) * doubled,)

    def plain_qkv(x, layer):
        h = (x * layer["w"])[:, None]
        return h, h, h

    def out(x, o, layer, rng):
        return x + o[:, 0], jnp.zeros((), jnp.float32)

    def dispatch(q, k, v):
        calls["dispatch"] += 1
        return v * 0.5

    pattern = stack.Pattern({"own": (own_qkv, out, own_attend), "plain": (plain_qkv, out)}, ("own", "plain", "own"), 2)
    w = jnp.linspace(0.5, 1.5, 6).reshape(2, 3)
    blocks = {"leading": [], "trailing": [], "period": [{"w": w[:, j]} for j in range(3)]}
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 8))
    got, aux = stack.apply_stack(blocks, x, Config(), pattern=pattern, attention_fn=dispatch)
    want = x
    for kind, layer in pattern.layers(blocks):
        h = want * layer["w"]
        want = want + (jnp.cumsum(h, axis=1) * layer["w"] * 2.0 if kind == "own" else h * 0.5)
    assert float(jnp.abs(got - want).max() / jnp.abs(want).max()) < 1e-6 and float(aux) == 0.0
    assert calls == {"own": 2, "dispatch": 1}  # traced once a place in the period, under one scan
    grads = jax.grad(lambda b: stack.apply_stack(b, x, Config(), pattern=pattern, attention_fn=dispatch)[0].sum())(blocks)
    assert all(float(jnp.abs(g["w"]).min()) > 0 for g in grads["period"])


def test_a_stack_wide_attend_stands_in_the_kinds_that_bring_none():
    calls = []

    def qkv(x, layer):
        return x[:, None], x[:, None], x[:, None], 1.0

    def wide(q, k, v, more, attention_fn, mesh):
        calls.append("wide")
        return (v,)

    def own(q, k, v, more, attention_fn, mesh):
        calls.append("own")
        return (v * 2.0,)

    import types

    config = types.SimpleNamespace(n_layer=2, remat=False, remat_policy=None, attention="xla")
    out = lambda x, o, layer, rng: (x + o[:, 0], jnp.zeros((), jnp.float32))  # noqa: E731
    pattern = stack.Pattern({"a": (qkv, out), "b": (qkv, out, own)}, ("a", "b"), 1)
    blocks = {"leading": [], "trailing": [], "period": [{"w": jnp.ones((1,))}, {"w": jnp.ones((1,))}]}
    got, _ = stack.apply_stack(blocks, jnp.ones((1, 3, 4)), config, pattern=pattern, attention_fn=None, attend=wide)
    assert calls == ["wide", "own"] and float(got[0, 0, 0]) == 6.0  # 1 + 1, then 2 + 2 x 2
