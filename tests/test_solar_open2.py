"""`models/solar_open2.py` against the plain reference of `benchmark/models/solar_open2.py` on seeded weights (loss and
every gradient leaf, in float32 at the nano size); the share test of the `model-configs` guide (the expert shares'
partial sums with the shared expert counted once add up to the uncut expert layer; the head shares' `W_o` outputs
add up to the uncut mixer, for both kinds); what the reference tells when a part of the mathematics is moved; the
sizes; and that the zoo's `__init__` does not import the module."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.models import solar_open2 as bench  # noqa: E402
from ray_tpu.models import solar_open2 as so  # noqa: E402
from ray_tpu.models.training import model_for  # noqa: E402
from ray_tpu.ops import kda  # noqa: E402

with open(os.path.join(REPO, "benchmark", "configs", "solar-open2-nano.json")) as fh:
    NANO = dict(json.load(fh), dtype="float32")
LIMIT = 2e-5  # a gradient leaf's distance from the reference's over its norm, in float32


def far(a, b):
    return float(jnp.linalg.norm((a - b).ravel()) / (jnp.linalg.norm(b.ravel()) + 1e-30))


@pytest.fixture(scope="module")
def seeded():
    cfg = bench.solar_open2_config(NANO)
    params = so.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 65), 0, NANO["vocab_size"] - 1)
    with jax.default_matmul_precision("highest"):
        reference = jax.jit(jax.value_and_grad(lambda p: bench.reference_loss(p, tokens, NANO), has_aux=True))(params)
    return cfg, params, tokens, reference


def test_the_model_is_the_reference_on_seeded_weights(seeded):
    cfg, params, tokens, ((want, stats), want_grads) = seeded
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(lambda p: so.loss_fn(p, {"tokens": tokens}, cfg)))(params)
    assert abs(float(loss) - float(want)) < 1e-5
    errors = jax.tree.map(far, grads, want_grads)
    worst = max(jax.tree_util.tree_flatten_with_path(errors)[0], key=lambda kv: kv[1])
    assert worst[1] < LIMIT, jax.tree_util.keystr(worst[0])
    assert 0.2 < float(stats["neg_eigval_share"]) < 0.8 and 0.0 < float(stats["decay_min"]) < 1.0
    assert stats["chosen"].shape == (4, 128, 16) and int(stats["chosen"].sum()) == 4 * 128 * 2


@pytest.mark.parametrize("fault", ["decay_bf16", "no_gqa_gate", "scalar_decay", "no_shared_expert", "no_renormalisation"])
def test_the_reference_tells_a_part_that_is_moved(seeded, fault):
    """The limit above is no formality: the reference with one part of the mathematics moved is further from
    the model than fifty times the limit at some leaf of the loss's gradient."""
    cfg, params, tokens, (_, want_grads) = seeded
    c, p = dict(NANO), params
    kwargs = {}
    if fault == "decay_bf16":
        kwargs["scan_dtype"] = jnp.bfloat16
    layers = p["blocks"]["period"]
    if fault == "no_gqa_gate":  # sigmoid(W_gate n) = 1/2 everywhere
        layers = [dict(layers[0], w_gate=jnp.zeros_like(layers[0]["w_gate"]))] + layers[1:]
    if fault == "scalar_decay":  # every channel of a head at the head's first channel's decay
        def first_channel(layer):
            up = layer["w_f_up"].reshape(1, NANO["kda_gate_rank"], 4, 16)
            bias = layer["dt_bias"].reshape(1, 4, 16)
            return dict(layer, w_f_up=jnp.broadcast_to(up[..., :1], up.shape).reshape(layer["w_f_up"].shape),
                        dt_bias=jnp.broadcast_to(bias[..., :1], bias.shape).reshape(layer["dt_bias"].shape))
        layers = [layers[0]] + [first_channel(layer) for layer in layers[1:]]
    if fault == "no_shared_expert":
        layers = [dict(layer, moe=dict(layer["moe"], shared_down=jnp.zeros_like(layer["moe"]["shared_down"])))
                  for layer in layers]
    if fault == "no_renormalisation":  # the chosen scores as they are, not over their sum
        c["norm_topk_prob"] = False
    moved = dict(p, blocks=dict(p["blocks"], period=layers))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(lambda q: bench.reference_loss(q, tokens, c, **kwargs)[0]))(moved)
    assert max(jax.tree.leaves(jax.tree.map(far, got, want_grads))) > 50 * LIMIT


def _layer(params, place):
    return jax.tree.map(lambda a: a[0], params["blocks"]["period"][place])


def test_expert_shares_add_up_to_the_uncut_layer():
    """A router over 40 experts, 2 a token, at the nano widths: the 40 shares of one expert each (`held_from`
    0, 1, ..., 39) give partial sums that add up to what the layer that holds all 40 gives; the shared expert
    is the same on every share and is counted once."""
    uncut = dataclasses.replace(so.SolarOpen2Config.nano(dtype=jnp.float32), n_experts=40, n_experts_held=None,
                                first_expert_held=0, layer_types=so.PERIOD)
    layer = _layer(so.init_params(uncut, jax.random.PRNGKey(2)), 0)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, uncut.d_model))
    with jax.default_matmul_precision("highest"):
        whole, shared, aux = so.feed_forward(x, layer, uncut)
        total, held = jnp.zeros_like(whole), 0
        for j in range(40):
            share = dataclasses.replace(uncut, n_experts_held=1, first_expert_held=j)
            mine = dict(layer, moe={name: (w[j:j + 1] if name in ("w_gate", "w_up", "w_down") else w)
                                    for name, w in layer["moe"].items()})
            routed, shared_here, aux_here = so.feed_forward(x, mine, share)
            assert far(shared_here, shared) < 1e-6
            total, held = total + routed, held + int(aux_here["held_pairs"])
    assert held == 2 * 32 * 2 and int(aux["held_pairs"]) == held  # every pair on exactly one share
    assert far(total + shared, whole + shared) < 1e-5


def _columns(w, heads, mine, axis):
    """The columns (or rows) of a projection onto `heads` heads that belong to the heads in `mine`."""
    shape = w.shape
    cut = w.reshape(*shape[:axis], heads, shape[axis] // heads, *shape[axis + 1:])
    cut = jnp.take(cut, jnp.asarray(mine), axis=axis)
    return cut.reshape(*shape[:axis], -1, *shape[axis + 1:])


def test_head_shares_add_up_to_the_uncut_mixer():
    """Both kinds: a share builds some of the heads (their columns of every projection onto heads, their rows
    of W_o; the low-rank down-projections and the norms whole), and the shares' W_o outputs add up to the
    uncut mixer's. The attention layer is shared a key/value head (with its group of query heads) at a time."""
    cfg = dataclasses.replace(so.SolarOpen2Config.nano(dtype=jnp.float32), layer_types=so.PERIOD)
    params = so.init_params(cfg, jax.random.PRNGKey(4))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 48, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        # the linear kind, a head a share
        layer, h = _layer(params, 1), cfg.linear_heads
        whole = so.kda_out(x, kda.kimi_delta_rule(*so.kda_qkv(x, layer, cfg), chunk=16), layer, cfg)
        total = jnp.zeros_like(whole)
        for j in range(h):
            share = dataclasses.replace(cfg, linear_heads=1)
            mine = dict(layer, **{name: _columns(layer[name], h, [j], layer[name].ndim - 1) for name in (
                "wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "w_f_up", "dt_bias", "A_log", "w_b", "w_g_up", "b_g")},
                wo=_columns(layer["wo"], h, [j], 0))
            total += so.kda_out(x, kda.kimi_delta_rule(*so.kda_qkv(x, mine, share), chunk=16), mine, share)
        assert far(total, whole) < 1e-5
        # the attention kind, a key/value head and its group a share
        from ray_tpu.ops.flash_attention import xla_attention

        layer, group = _layer(params, 0), cfg.n_head // cfg.n_kv_head
        whole = so.gqa_out(x, xla_attention(*so.gqa_qkv(x, layer, cfg), causal=True), layer, cfg)
        total = jnp.zeros_like(whole)
        for j in range(cfg.n_kv_head):
            share = dataclasses.replace(cfg, n_head=group, n_kv_head=1)
            queries = slice(j * group, (j + 1) * group)
            mine = dict(layer, wq=layer["wq"][:, queries], w_gate=layer["w_gate"][:, queries], wo=layer["wo"][queries],
                        wk=layer["wk"][:, j:j + 1], wv=layer["wv"][:, j:j + 1])
            total += so.gqa_out(x, xla_attention(*so.gqa_qkv(x, mine, share), causal=True), mine, share)
        assert far(total, whole) < 1e-5


def test_sizes_and_where_the_module_lives():
    cfg = so.SolarOpen2Config.nano()
    params = jax.eval_shape(lambda: so.init_params(cfg, jax.random.PRNGKey(0)))
    assert so.num_params(cfg) == sum(x.size for x in jax.tree.leaves(params))
    axes = so.param_logical_axes(cfg)
    is_axes = lambda x: isinstance(x, tuple) and all(a is None or isinstance(a, str) for a in x)  # noqa: E731
    assert jax.tree.structure(axes, is_leaf=is_axes) == jax.tree.structure(params)
    assert all(len(a) == p.ndim for a, p in zip(jax.tree.leaves(axes, is_leaf=is_axes), jax.tree.leaves(params)))
    assert model_for(cfg) is so
    published = so.SolarOpen2Config()
    assert published.period == so.PERIOD and published.n_layer == 48 and published.held == 320
    assert so.train_flops_per_token(cfg, 64) > 0
    # The zoo's `__init__` does not import it: no other cell's set-up pays for it.
    out = subprocess.run(
        [sys.executable, "-c", "import sys, ray_tpu.models; print('ray_tpu.models.solar_open2' in sys.modules, "
         "'ray_tpu.ops.kda' in sys.modules)"], cwd=REPO, capture_output=True, text=True, check=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.stdout.split() == ["False", "False"]


def test_routing_stats_count_every_pair(seeded):
    cfg, params, tokens, ((_, stats), _) = seeded
    got = jax.jit(lambda p, t: so.routing_stats(p, t, cfg))(params, tokens[:, :-1])
    pairs = tokens[:, :-1].size * cfg.experts_per_token
    assert got["experts"].shape == (4, 128, 2) and got["tokens_per_expert"].shape == (4, 16)
    assert (got["held_pairs"] + got["elsewhere_pairs"] == pairs).all() and int(got["dropped"].sum()) == 0
    same = jnp.take_along_axis(stats["chosen"], got["experts"], axis=-1)
    assert bool(same.all())  # in float32 the model and the reference choose the same experts
