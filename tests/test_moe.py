"""`models/moe.py moe_mlp` against an expert layer spelled out in float32:
every expert on every token, and the router's weight applied *after* the down
projection, where the definition puts it. `moe_mlp` applies it one product
earlier, to the rows SwiGLU writes, and sends it through the layer's own
permutation; output and every gradient must not know the difference, for one
expert a token and for eight, with and without renormalised weights, and
through `GPTConfig.moe_experts`. Then a layer that holds some of the experts:
its sorted form is a prefix of the sort while the held pairs fit a bound, and
that form must be the whole-length one to rounding, on both sides of the bound."""

import functools
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

TOKENS, D, F, E = (2, 24), 16, 32, 16
WEIGHTS = ("router_w", "w_gate", "w_up", "w_down")


def spelled_out(x, router_w, w_gate, w_up, w_down, *, k, norm_topk_prob=False):
    """(out, aux) as `moe_mlp` gives them, the dense way, all in float32."""
    import jax
    import jax.numpy as jnp

    tokens = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    probs = jax.nn.softmax(tokens @ router_w.astype(jnp.float32), axis=-1)
    weights, experts = jax.lax.top_k(probs, k)
    if norm_topk_prob:
        weights = weights / weights.sum(axis=-1, keepdims=True)
    chosen = jax.nn.one_hot(experts, probs.shape[-1], dtype=jnp.float32)  # (T, k, E)
    per_expert = jnp.einsum("tk,tke->te", weights, chosen)
    hidden = (jax.nn.silu(jnp.einsum("td,edf->tef", tokens, w_gate.astype(jnp.float32)))
              * jnp.einsum("td,edf->tef", tokens, w_up.astype(jnp.float32)))
    results = jnp.einsum("tef,efd->ted", hidden, w_down.astype(jnp.float32))
    out = jnp.einsum("te,ted->td", per_expert, results)
    load = chosen.sum(axis=(0, 1)) / tokens.shape[0]
    aux = {"load_balance": probs.shape[-1] * jnp.sum(load * probs.mean(axis=0))}
    return out.reshape(x.shape).astype(x.dtype), aux


@functools.lru_cache(maxsize=None)
def _both(k, norm_topk_prob):
    """{name: (moe_mlp's, the spelled-out layer's)} for the output and for the
    gradient, by each weight, of the output against one fixed cotangent."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.moe import moe_mlp

    keys = jax.random.split(jax.random.PRNGKey(k), 6)
    x = jax.random.normal(keys[0], (*TOKENS, D))
    cotangent = jax.random.normal(keys[1], (*TOKENS, D))
    params = {"router_w": jax.random.normal(keys[2], (D, E)),
              "w_gate": jax.random.normal(keys[3], (E, D, F)) / np.sqrt(D),
              "w_up": jax.random.normal(keys[4], (E, D, F)) / np.sqrt(D),
              "w_down": jax.random.normal(keys[5], (E, F, D)) / np.sqrt(F)}

    def run(layer):
        def scalar(p):
            out, _ = layer(x, *(p[name] for name in WEIGHTS), k=k, norm_topk_prob=norm_topk_prob)
            return jnp.sum(out * cotangent), out

        (_, out), grads = jax.jit(jax.value_and_grad(scalar, has_aux=True))(params)
        return {"out": out, **grads}

    got, want = run(moe_mlp), run(spelled_out)
    return {name: (np.asarray(got[name]), np.asarray(want[name])) for name in got}


@pytest.mark.parametrize("what", ("out",) + WEIGHTS)
@pytest.mark.parametrize("norm_topk_prob", (False, True), ids=("softmax_weights", "renormalised"))
@pytest.mark.parametrize("k", (1, 8))
def test_moe_mlp_is_the_layer_with_the_weight_after_the_down_projection(k, norm_topk_prob, what):
    got, want = _both(k, norm_topk_prob)[what]
    assert np.abs(want).max() > (0 if what == "router_w" and k == 1 and norm_topk_prob else 1e-2)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_the_router_learns_only_through_the_weights_it_gave():
    """One renormalised expert a token weighs 1 whatever the router says: no
    gradient reaches `router_w`, in either form. With the softmax's own
    weight it does, and that is the path through `_sort_weights`."""
    assert max(np.abs(g).max() for g in _both(1, True)["router_w"]) < 1e-6  # w / w, to rounding
    assert np.abs(_both(1, False)["router_w"][0]).max() > 1e-2


def test_in_bf16_the_weighting_costs_no_rounding_of_its_own():
    """bf16 rows, float32 weighting: the layer stays within bf16's rounding of
    the products (2^-8 of values of order 1, three products deep) of the
    float32 layer on the same bf16 inputs."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.moe import moe_mlp

    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(keys[0], (*TOKENS, D)).astype(jnp.bfloat16)
    weights = (jax.random.normal(keys[1], (D, E)), jax.random.normal(keys[2], (E, D, F)) / np.sqrt(D),
               jax.random.normal(keys[3], (E, D, F)) / np.sqrt(D),
               jax.random.normal(keys[4], (E, F, D)) / np.sqrt(F))
    got, _ = moe_mlp(x, *weights, k=8)
    want, _ = spelled_out(x.astype(jnp.float32), weights[0],
                          *(w.astype(jnp.bfloat16) for w in weights[1:]), k=8)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), atol=3e-2)


def test_through_gpt_config_moe_experts_loss_and_gradients_agree(monkeypatch):
    """`models/gpt.py` runs the same `moe_mlp` with one expert a token: the
    nano model's loss (auxiliary term included) and the gradient of every
    parameter, with the layer as it is and with the spelled-out one in its place."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import GPTConfig, gpt, moe

    cfg = GPTConfig.nano(dtype=jnp.float32, moe_experts=4, remat=False)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.asarray(np.random.default_rng(0).integers(0, 255, (2, 33), np.int32))}

    def loss_and_grads():
        return jax.value_and_grad(lambda p: gpt.loss_fn(p, batch, cfg))(params)

    loss, grads = loss_and_grads()
    monkeypatch.setattr(moe, "moe_mlp", spelled_out)
    want_loss, want_grads = loss_and_grads()
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    assert float(jnp.abs(want_grads["blocks"]["moe"]["router_w"]).max()) > 1e-4
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6),
                 grads, want_grads)


# ------------------------------------------- a share of the experts: the prefix form and the whole one
SHARE_TOKENS, SHARE_E, SHARE_HELD, SHARE_FROM = 1024, 16, 2, 2  # 2,048 pairs: the bound is 512 rows
BOUND = 512
HELD_PAIRS = {"below_the_bound": 300, "at_the_bound": BOUND, "one_over_the_bound": BOUND + 1,
              "every_pair_held": 2 * SHARE_TOKENS}


@functools.lru_cache(maxsize=None)
def _share(routing, whole_length):
    """Output, gradients and `aux` of a layer that holds experts 2 and 3 of 16,
    two a token, where `HELD_PAIRS[routing]` of the pairs are its own: as
    `moe_mlp` runs it, or with the bound lifted to every pair (the whole-length
    form, and no `cond`)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import moe

    held_pairs = HELD_PAIRS[routing]
    both, one = held_pairs // 2, held_pairs % 2  # tokens with both choices held here, with one
    keys = jax.random.split(jax.random.PRNGKey(7), 7)
    # The first three features say where a token goes (both held / one held / none); the rest is noise.
    kind = jnp.where(jnp.arange(SHARE_TOKENS) < both, 0, jnp.where(jnp.arange(SHARE_TOKENS) < both + one, 1, 2))
    x = jnp.concatenate([6.0 * jax.nn.one_hot(kind, 3), jax.random.normal(keys[0], (SHARE_TOKENS, D - 3))], axis=1)
    x = x.reshape(2, SHARE_TOKENS // 2, D)
    pull = np.zeros((D, SHARE_E), np.float32)
    pull[0, [2, 3]] = pull[1, [3, 9]] = pull[2, [11, 12]] = 1.0
    params = {"router_w": jnp.asarray(pull) + 0.1 * jax.random.normal(keys[1], (D, SHARE_E)),
              "w_gate": jax.random.normal(keys[2], (SHARE_HELD, D, F)) / np.sqrt(D),
              "w_up": jax.random.normal(keys[3], (SHARE_HELD, D, F)) / np.sqrt(D),
              "w_down": jax.random.normal(keys[4], (SHARE_HELD, F, D)) / np.sqrt(F)}
    cotangent = jax.random.normal(keys[5], x.shape)

    def scalar(x, p):
        out, aux = moe.moe_mlp(x, *(p[name] for name in WEIGHTS), k=2, norm_topk_prob=True,
                               held_from=SHARE_FROM)
        return jnp.sum(out * cotangent), (out, aux)

    with pytest.MonkeyPatch.context() as patch:
        if whole_length:
            patch.setattr(moe, "held_row_bound", lambda pairs, n_held, n_experts: pairs)
        assert moe.held_row_bound(2 * SHARE_TOKENS, SHARE_HELD, SHARE_E) == (
            2 * SHARE_TOKENS if whole_length else BOUND)
        (_, (out, aux)), (dx, grads) = jax.jit(jax.value_and_grad(scalar, argnums=(0, 1), has_aux=True))(x, params)
    return {"out": out, "x": dx, **grads}, aux


@pytest.mark.parametrize("what", ("out", "x") + WEIGHTS)
@pytest.mark.parametrize("routing", HELD_PAIRS)
def test_the_prefix_form_is_the_whole_length_form_on_both_sides_of_the_bound(routing, what):
    (got, aux), (want, whole_aux) = _share(routing, False), _share(routing, True)
    assert int(aux["held_pairs"]) == int(whole_aux["held_pairs"]) == HELD_PAIRS[routing]
    assert int(aux["rows_processed"]) == HELD_PAIRS[routing]  # dropless in the form that ran
    assert bool(aux["compact"]) == (HELD_PAIRS[routing] <= BOUND) and not bool(whole_aux["compact"])
    assert np.abs(np.asarray(want[what])).max() > 1e-3
    np.testing.assert_allclose(np.asarray(got[what]), np.asarray(want[what]), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("pairs, n_held, n_experts, rows", [
    (131072, 8, 64, 32768),  # the LFM2 cell: a quarter
    (2048, 2, 16, 512), (2048, 1, 16, 512),  # whole row tiles
    (65536, 64, 64, 65536), (256, 2, 8, 256), (2048, 8, 16, 2048)])  # no more than every pair
def test_the_bound_is_twice_the_even_share_in_whole_row_tiles(pairs, n_held, n_experts, rows):
    from ray_tpu.models.moe import held_row_bound

    assert held_row_bound(pairs, n_held, n_experts) == rows



@functools.lru_cache(maxsize=None)
def _share_at_kernel_shapes(through_the_kernels, cotangent_through_gather_rows=False):
    """Output, gradients and `aux` of a layer that holds expert 5 of 8 at
    shapes the Pallas kernels take (512 tokens of 128, two a token: 1,024
    pairs, a bound of 512 rows), through the kernels in interpret mode or
    through the XLA forms. `cotangent_through_gather_rows`: VMEM's size is
    patched down to just under twice the tokens' bytes, so that the cotangent's
    gather is `gather_rows` and the tokens' stays XLA's (`moe._rows_by`); what
    that kernel does not write is NaN, and `aux["gathered_by_the_kernel"]`
    lists the sources it was handed."""
    import jax
    from jax.experimental.pallas import tpu as pltpu

    from ray_tpu.models import moe
    from ray_tpu.ops.grouped_matmul import grouped_matmul
    from ray_tpu.ops.sum_rows import gather_rows, sum_rows

    tokens, d, f = 512, 128, 128
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    x = jax.random.normal(keys[0], (1, tokens, d))
    cotangent = jax.random.normal(keys[1], x.shape)
    weights = (jax.random.normal(keys[2], (d, 8)), jax.random.normal(keys[3], (1, d, f)) / d ** 0.5,
               jax.random.normal(keys[4], (1, d, f)) / d ** 0.5, jax.random.normal(keys[5], (1, f, d)) / f ** 0.5)
    backend = dict(backend="pallas", interpret=True) if through_the_kernels else dict(backend="xla")
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "sum_rows", functools.partial(sum_rows, **backend))
        patch.setattr(moe, "grouped_matmul", functools.partial(grouped_matmul, **backend))
        # A jit of its own: `moe._prefix_or_whole_jit` would hand back the trace of whichever of these ran first.
        patch.setattr(moe, "_prefix_or_whole_jit", jax.jit(
            lambda *operands: moe._prefix_or_whole(*operands), static_argnums=(0, 1, 2)))
        gathered = []
        if cotangent_through_gather_rows:
            unwritten_is_nan = pltpu.InterpretParams(uninitialized_memory="nan")
            patch.setattr(moe, "gather_rows", lambda x, *plan: gathered.append(x.shape) or gather_rows(
                x, *plan, backend="pallas", interpret=unwritten_is_nan))
            patch.setattr(moe, "GATHER_SOURCE_BYTES", 2 * tokens * d * x.dtype.itemsize - 1)
        out, vjp, aux = jax.vjp(lambda x, *w: moe.moe_mlp(x, *w, k=2, held_from=5), x, *weights, has_aux=True)
        return [np.asarray(a) for a in (out, *vjp(cotangent))], {**aux, "gathered_by_the_kernel": gathered}


@pytest.mark.parametrize("what", range(6), ids=("out", "x") + WEIGHTS)
def test_the_prefix_form_is_the_same_through_the_kernels(what):
    """`grouped_matmul(short=True)` and `sum_rows` take the 512 rows of the
    prefix (the kernels' visits and runs stop at the held pairs either way)."""
    (got, aux), (want, _) = _share_at_kernel_shapes(True), _share_at_kernel_shapes(False)
    assert bool(aux["compact"]) and 0 < int(aux["held_pairs"]) == int(aux["rows_processed"]) <= 512
    assert np.abs(want[what]).max() > 1e-3
    np.testing.assert_allclose(got[what], want[what], rtol=1e-4, atol=1e-4)


# ------------------------------ scalars of the pairs move through sorts and compares: the same numbers, exactly
def gathered_route(x, router_w, k, norm_topk_prob=False, *, bias=None):
    """`models/moe.py route` as it stood until PR 38, the reference: the picked
    scores are `top_k`'s own values or a `take_along_axis` (a gather of
    `tokens * k` scalars, a scatter-add backward), `counts` a scatter-add."""
    import jax
    import jax.numpy as jnp

    n_experts = router_w.shape[-1]
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router_w.astype(jnp.float32))
    if bias is None:
        probs = jax.nn.softmax(logits, axis=-1)
        weights, experts = jax.lax.top_k(probs, k)
    else:
        probs = jax.nn.sigmoid(logits)
        _, experts = jax.lax.top_k(probs + jax.lax.stop_gradient(bias.astype(jnp.float32)), k)
        weights = jnp.take_along_axis(probs, experts, axis=-1)
    if norm_topk_prob:
        weights = weights / weights.sum(axis=-1, keepdims=True)
    counts = jnp.zeros((n_experts,), jnp.int32).at[experts.reshape(-1)].add(1)
    aux = {"load_balance": n_experts * jnp.sum(counts.astype(jnp.float32) / x.shape[0] * probs.mean(axis=0)),
           "z": jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2),
           "tokens_per_expert": counts}
    return weights, experts, aux


def gathered_order(experts, weights, n):
    """`expert_order` and `_sort_weights` as they stood until PR 38: (ids,
    order, inverse, the first `n` weights in sorted order) by `argsort`, a
    scatter of an iota and gathers; the weights' gradient is a gather by
    `inverse` that fills with zeros behind the prefix."""
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def sort_weights(weights, order, inverse):
        return weights[order]

    def bwd(inverse, g):
        return g.at[inverse].get(mode="fill", fill_value=0), None, None

    sort_weights.defvjp(lambda weights, order, inverse: (weights[order], inverse), bwd)
    flat = experts.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    inverse = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0], dtype=order.dtype))
    return flat[order], order, inverse, sort_weights(weights.reshape(-1), order[:n], inverse)


SCALAR_CASES = {  # tokens, experts, k, input dtype, with a selection bias, renormalised
    "f32_k4_of_16": (96, 16, 4, "float32", False, False),
    "bf16_k2_of_8_bias": (64, 8, 2, "bfloat16", True, True),
    "f32_k1_of_4": (40, 4, 1, "float32", False, False),
    "f32_k1_of_4_bias": (40, 4, 1, "float32", True, False),
    "bf16_k8_of_64_renormalised": (128, 64, 8, "bfloat16", False, True),
    "f32_k4_of_64_bias": (128, 64, 4, "float32", True, True),
}
SCALAR_WHATS = ("experts", "weights", "counts", "load_balance", "z", "d_x", "d_router_w",
                "ids", "order", "inverse", "sorted_weights", "d_weights", "sorted_prefix", "d_weights_behind_a_prefix")


@functools.lru_cache(maxsize=None)
def _scalar_forms(case):
    """{what: (as `models/moe.py` moves it, as the gather / scatter forms did)}
    for one routing: `route`'s outputs and its gradients by the tokens and the
    router's matrix (through the weights and both auxiliary terms), then the
    sort of its pairs, the weights in sorted order and their gradient at whole
    length and for a prefix that leaves a third of the pairs behind it. Expert
    1 gets no token (its column of the router pulls away from every token);
    every other expert gets many, so the ids are full of ties."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import moe

    tokens, n_experts, k, dtype, with_bias, renormalised = SCALAR_CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 6)
    x = jax.random.normal(keys[0], (tokens, D)).astype(dtype)
    router_w = jax.random.normal(keys[1], (D, n_experts)).at[:, 1].set(0.0).astype(dtype)
    bias = 0.3 * jax.random.normal(keys[2], (n_experts,)).at[1].set(-50.0) if with_bias else None
    if not with_bias:  # softmax scores: the second feature is large and positive, its column of the router negative
        x = x.at[:, 1].set(8.0)
        router_w = router_w.at[1, 1].set(-8.0)
    cot = jax.random.normal(keys[3], (tokens, k))
    pairs = tokens * k
    prefix = pairs * 2 // 3
    sorted_cot = jax.random.normal(keys[4], (pairs,))

    def both(route, order_of):
        def scalar(x, router_w):
            weights, experts, aux = route(x, router_w, k, renormalised, bias=bias)
            return jnp.sum(weights * cot) + 0.3 * aux["load_balance"] + 0.1 * aux["z"], (weights, experts, aux)

        (_, (weights, experts, aux)), (d_x, d_router_w) = jax.value_and_grad(
            scalar, argnums=(0, 1), has_aux=True)(x, router_w)
        out = {"experts": experts, "weights": weights, "counts": aux["tokens_per_expert"],
               "load_balance": aux["load_balance"], "z": aux["z"], "d_x": d_x, "d_router_w": d_router_w}
        for n, value, gradient in ((pairs, "sorted_weights", "d_weights"),
                                   (prefix, "sorted_prefix", "d_weights_behind_a_prefix")):
            (ids, order, inverse, out[value]), vjp = jax.vjp(lambda w: order_of(experts, w, n), weights)
            out[gradient] = vjp((np.zeros(pairs, jax.dtypes.float0),) * 3 + (sorted_cot[:n],))[0]
        return {**out, "ids": ids, "order": order, "inverse": inverse}

    def sorted_order(experts, weights, n):
        ids, order, inverse, weights = moe.expert_order(experts, weights)
        return ids, order, inverse, weights[:n]

    got, want = both(moe.route, sorted_order), both(gathered_route, gathered_order)
    assert int(want["counts"][1]) == 0 and int(want["counts"].max()) > 1  # an empty expert, and ties
    return {what: (np.asarray(got[what]), np.asarray(want[what])) for what in SCALAR_WHATS}


@pytest.mark.parametrize("what", SCALAR_WHATS)
@pytest.mark.parametrize("case", SCALAR_CASES)
def test_scalars_move_through_sorts_and_compares_and_not_a_bit_changes(case, what):
    """Until PR 38 the layer gathered and scattered its `tokens * k` scalars one
    4-byte element at a time (7 ns each on the v5e); now every such movement is
    an operand of a sort or a compare against an iota of E. Values only move
    and only exact zeros are added, so every output and gradient is the same
    array (`array_equal`, not `allclose`): for float32 and bfloat16 inputs, one
    expert a token and eight, softmax scores and sigmoids with a bias, an
    expert that no token chose, and pairs sorted behind a prefix."""
    got, want = _scalar_forms(case)[what]
    assert got.dtype == want.dtype and got.shape == want.shape
    if what.startswith("d_") or what in ("weights", "sorted_weights", "sorted_prefix"):
        assert np.abs(want).max() > 1e-3
    if what == "d_weights_behind_a_prefix":  # a third of the pairs lie behind the prefix: zeros
        assert (want == 0).sum() >= want.size // 3
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------------------------ two tensors, and the activation a model hands (PR 70)
def two_tensor_layer(tap, x, router_w, w_gate, w_up, w_down, *, k, act, held=None):
    """The layer whose router reads `tap` and whose experts read `x` (tokens as rows), the dense way in float32: a
    softmax over the k chosen logits of `tap`, `act` on the gate, every expert in `held` on every token."""
    import jax
    import jax.numpy as jnp

    probs = jax.nn.softmax(tap @ router_w, axis=-1)
    weights, experts = jax.lax.top_k(probs, k)
    weights = weights / weights.sum(axis=-1, keepdims=True)
    per_expert = jnp.einsum("tk,tke->te", weights, jax.nn.one_hot(experts, probs.shape[-1], dtype=jnp.float32))
    held = held or range(probs.shape[-1])
    hidden = act(jnp.einsum("td,edf->tef", x, w_gate)) * jnp.einsum("td,edf->tef", x, w_up)
    return jnp.einsum("te,ted->td", per_expert[:, held.start:held.stop], jnp.einsum("tef,efd->ted", hidden, w_down))


@functools.lru_cache(maxsize=None)
def _two_tensors(act_name, held, tokens=TOKENS[0] * TOKENS[1], k=4):
    """{name: (the two halves', the dense layer's)}: the output, and its gradient by the router's tensor, the experts'
    tensor and each weight, against one fixed cotangent. `held`: None, or (first, count) of the 16 experts."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.moe import experts_of, route_and_sort

    act = {"silu": jax.nn.silu, "relu": jax.nn.relu}[act_name]
    keys = jax.random.split(jax.random.PRNGKey(11), 7)
    tap, x, cotangent = (jax.random.normal(key, (tokens, D)) for key in keys[:3])
    mine = range(E) if held is None else range(held[0], held[0] + held[1])
    params = {"router_w": jax.random.normal(keys[3], (D, E)),
              "w_gate": jax.random.normal(keys[4], (len(mine), D, F)) / np.sqrt(D),
              "w_up": jax.random.normal(keys[5], (len(mine), D, F)) / np.sqrt(D),
              "w_down": jax.random.normal(keys[6], (len(mine), F, D)) / np.sqrt(F)}

    def halves(tap, x, p):
        routing, aux = route_and_sort(tap, p["router_w"], len(mine), k=k, norm_topk_prob=True, held_from=mine.start)
        out, report = experts_of(x, routing, p["w_gate"], p["w_up"], p["w_down"], k=k, n_experts=E, act=act)
        return out, {**aux, **report}

    def dense(tap, x, p):
        return two_tensor_layer(tap, x, **p, k=k, act=act, held=mine), None

    got = {}
    for name, f in (("halves", halves), ("dense", dense)):
        out, aux = f(tap, x, params)
        grads = jax.grad(lambda tap, x, p: jnp.sum(f(tap, x, p)[0] * cotangent), argnums=(0, 1, 2))(tap, x, params)
        got[name] = {"out": out, "tap": grads[0], "x": grads[1], **grads[2]}
        if aux is not None:
            got["aux"] = aux
    return got


@pytest.mark.parametrize("what", ("out", "tap", "x") + WEIGHTS)
@pytest.mark.parametrize("act_name,held,tokens,k", [("relu", None, 48, 4), ("silu", None, 48, 4), ("relu", (4, 4), 48, 4),
                                                    ("relu", (2, 2), 1024, 2)],
                         ids=("relu", "silu", "relu_held_4_of_16", "relu_in_the_held_prefix"))
def test_the_two_halves_route_on_one_tensor_and_compute_on_another(act_name, held, tokens, k, what):
    """`route_and_sort` on the router's tensor and `experts_of` on the experts': the output and every gradient are the
    dense layer's, the router's weight learns through `tap` alone and `tap` through the weights alone, with the
    activation the model hands, for a layer that holds experts 4-7 of 16, and through the held prefix of the sort with
    its hand-written backward rule (2 of 16 held, 2,048 pairs of which the first 512 rows are the sorted form)."""
    got = _two_tensors(act_name, held, tokens, k)
    if tokens == 1024:
        assert bool(got["aux"]["compact"]) and 100 < int(got["aux"]["held_pairs"]) <= 512
    mine, theirs = np.asarray(got["halves"][what]), np.asarray(got["dense"][what])
    assert np.abs(theirs).max() > 1e-3, what
    np.testing.assert_allclose(mine, theirs, atol=2e-5 * max(1.0, np.abs(theirs).max()))


def test_the_activation_is_the_one_handed_and_silu_by_default():
    import jax

    relu, silu = _two_tensors("relu", None), _two_tensors("silu", None)
    assert np.abs(np.asarray(relu["halves"]["out"]) - np.asarray(silu["halves"]["out"])).max() > 0.05
    aux = relu["aux"]
    assert int(aux["held_pairs"]) == int(aux["rows_processed"]) == TOKENS[0] * TOKENS[1] * 4 and not bool(aux["compact"])
    # `moe_mlp` is the two halves on one tensor, SiLU unless told: the existing cases above hold it to the spelled-out
    # SwiGLU layer; with `act=jax.nn.relu` it is the ReGLU layer.
    from ray_tpu.models.moe import moe_mlp, swiglu

    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(keys[0], (*TOKENS, D))
    p = {"router_w": jax.random.normal(keys[1], (D, E)), "w_gate": jax.random.normal(keys[2], (E, D, F)) / np.sqrt(D),
         "w_up": jax.random.normal(keys[3], (E, D, F)) / np.sqrt(D), "w_down": jax.random.normal(keys[4], (E, F, D)) / np.sqrt(F)}
    flat = x.reshape(-1, D)
    for act in (jax.nn.silu, jax.nn.relu):
        out, _ = moe_mlp(x, **p, k=4, norm_topk_prob=True, act=act)
        np.testing.assert_allclose(np.asarray(out).reshape(-1, D), np.asarray(two_tensor_layer(flat, flat, **p, k=4, act=act)),
                                   atol=2e-5)
    default, _ = moe_mlp(x, **p, k=4, norm_topk_prob=True)
    assert np.array_equal(np.asarray(default), np.asarray(moe_mlp(x, **p, k=4, norm_topk_prob=True, act=jax.nn.silu)[0]))
    one = {name: p[name][0] for name in ("w_gate", "w_up", "w_down")}
    dense = lambda act: (act(flat @ one["w_gate"]) * (flat @ one["w_up"])) @ one["w_down"]  # noqa: E731
    np.testing.assert_allclose(np.asarray(swiglu(x, **one)).reshape(-1, D), np.asarray(dense(jax.nn.silu)), atol=2e-5)
    np.testing.assert_allclose(np.asarray(swiglu(x, **one, act=jax.nn.relu)).reshape(-1, D), np.asarray(dense(jax.nn.relu)), atol=2e-5)
