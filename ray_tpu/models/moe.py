"""Mixture-of-experts feed-forward layer: token-choice top-k routing, dropless.

Every token picks its `k` experts by the router's scores (`route`: a softmax,
or sigmoids with a selection bias, as the model's source has it); the `tokens * k`
(token, expert) pairs are sorted by expert, the rows are gathered into expert
order, and the SwiGLU experts run as three grouped matmuls over the ragged
groups of rows (`ops/grouped_matmul.py`). The router's probability of each
pair is applied to its row where SwiGLU's output is written, the operand of
the down projection (`w * (a @ W) = (w * a) @ W`, row by row), so the results
are only summed per token (`ops/sum_rows.py`: on the TPU a kernel that reads
each token's rows where the sort left them and never writes them in token
order): no pass over the `tokens * k` rows exists for the weighting alone,
forward or backward. There
is no capacity and no dropped token, and no tensor with both a token and an
expert-slot axis: work and memory are linear in tokens (the Switch layer this
replaces went through dense `(B, S, E, C)` one-hots, three times the experts'
own FLOPs at 64 experts).

Expert weights carry the `expert` logical axis, so a mesh with an `expert`
axis shards them; the sorted form is partitioned by XLA from the sharding
annotations alone (correct on any mesh).

A layer may hold a range of the experts and not all (`moe_mlp(held_from=)`,
the expert weights leading with the held count): one chip's share of an
expert-parallel deployment. The router still scores every expert. The pairs
whose expert lies elsewhere sort behind the held groups, no grouped matmul
visits them, `sum_rows` is never pointed at them, and what those experts
would have added to a token is left out: the layer returns the partial sum
that this share computes. The exchange that would send those pairs to their
chips and bring the other chips' partial sums back does not exist yet
(ROADMAP: "an expert exchange across chips").
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.grouped_matmul import grouped_matmul
from ray_tpu.ops.sum_rows import sorted_runs, sum_rows


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _gather_rows(x, order, inverse, runs, k: int):
    """Row `order[i] // k` of `x` for every i: the tokens in expert order.
    `order` is a permutation of the `tokens * k` pairs and `inverse` undoes
    it, so the gradient sums each token's `k` rows where they lie
    (`_sum_rows`; `runs` says where, `ops/sum_rows.py sorted_runs`), where the
    transpose jax would derive is a scatter-add of `tokens * k` rows."""
    return x[order // k]


def _gather_rows_fwd(x, order, inverse, runs, k):
    return x[order // k], (order, inverse, runs)


def _gather_rows_bwd(k, res, g):
    return _sum_rows(g, *res, k), None, None, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _sum_rows(rows, order, inverse, runs, k: int):
    """The transpose of `_gather_rows`: each token's `k` rows summed (in
    float32), the sums in token order."""
    return sum_rows(rows, inverse, runs, k)


def _sum_rows_fwd(rows, order, inverse, runs, k):
    return sum_rows(rows, inverse, runs, k), (order, inverse, runs)


def _sum_rows_bwd(k, res, g):
    return _gather_rows(g, *res, k), None, None, None


@jax.custom_vjp
def _sort_weights(weights, order, inverse):
    """The `tokens * k` weights, one per pair, in the order of the sorted
    rows. The gradient is a gather by `inverse`, where the transpose jax
    would derive is a scatter-add of `tokens * k` updates."""
    return weights[order]


def _sort_weights_fwd(weights, order, inverse):
    return weights[order], inverse


def _sort_weights_bwd(inverse, g):
    return g[inverse], None, None


_gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)
_sum_rows.defvjp(_sum_rows_fwd, _sum_rows_bwd)
_sort_weights.defvjp(_sort_weights_fwd, _sort_weights_bwd)


def route(x, router_w, k: int, norm_topk_prob: bool = False, *, bias=None, scale: float = 1.0):
    """x: (T, D). Returns (weights (T, k) f32, experts (T, k) int32, aux).

    Logits and softmax in float32; the weights of the chosen experts are the
    softmax's own (renormalised to sum to one only with `norm_topk_prob`).
    With `bias` (E,), the other scoring (DeepSeek-V3's, LFM2's): the scores are
    sigmoids of the logits, each by itself; the k experts are the largest of
    `score + bias`, the bias entering the choice only (no gradient reaches
    it: it is a buffer that a balancing rule outside the loss would move);
    the weights are the scores themselves at the chosen, renormalised with
    `norm_topk_prob`, times `scale`.
    `aux` holds the two auxiliary terms of Muennighoff et al. 2024 and the
    load they are computed from (what a caller does not use of it, the
    compiler drops): `load_balance` = E * sum_e f_e * P_e with
    f_e the tokens routed to expert e over the number of tokens (so the f_e
    sum to k) and P_e the mean router probability of e; `z` = mean of
    logsumexp(logits)^2; `tokens_per_expert` (E,) int32."""
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
    if scale != 1.0:
        weights = weights * scale
    counts = jnp.zeros((n_experts,), jnp.int32).at[experts.reshape(-1)].add(1)
    aux = {
        "load_balance": n_experts * jnp.sum(
            counts.astype(jnp.float32) / x.shape[0] * probs.mean(axis=0)),
        "z": jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2),
        "tokens_per_expert": counts,
    }
    return weights, experts, aux


def expert_order(experts):
    """(order, inverse) for `experts` (T, k): `order` lists the `T * k`
    (token, slot) pairs by expert (stable, so by token inside an expert),
    `inverse` is where each pair went."""
    order = jnp.argsort(experts.reshape(-1), stable=True)
    inverse = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0], dtype=order.dtype))
    return order, inverse


def moe_mlp(
    x,  # (B, S, D) activations, config.dtype
    router_w,  # (D, E)
    w_gate,  # (E, D, F), or (H, D, F) where the layer holds H of the E experts
    w_up,  # likewise
    w_down,  # (E, F, D), or (H, F, D)
    *,
    k: int,
    norm_topk_prob: bool = False,
    router_bias=None,  # (E,): `route`'s other scoring
    weight_scale: float = 1.0,
    held_from: int = 0,  # the first expert held, where H < E
) -> Tuple[Any, Dict[str, Any]]:
    """Returns (out (B, S, D), aux): `out = sum over the token's k experts of
    p_e * W_down,e (silu(W_gate,e h) * W_up,e h)`, `aux` as `route` gives it
    plus `experts` (tokens, k), each token's choices, `rows_processed`,
    the rows of the sorted form that their own expert takes, and `held_pairs`,
    the (token, expert) pairs whose expert this layer holds.
    The scopes are read from a device trace by the benchmark's `moe.*_ms`.

    Where the expert weights hold fewer experts than the router scores, they
    are experts `held_from .. held_from + H` and the sum runs over those of
    the token's k that are among them (the module's docstring): `out` is this
    share's partial sum."""
    B, S, D = x.shape
    cdt = x.dtype
    n_experts, n_held = router_w.shape[-1], w_gate.shape[0]
    partial = n_held < n_experts
    tokens = x.reshape(B * S, D)
    with jax.named_scope("router"):
        weights, experts, aux = route(tokens, router_w, k, norm_topk_prob,
                                      bias=router_bias, scale=weight_scale)
        aux["experts"] = experts
    with jax.named_scope("dispatch"):
        sizes = aux["tokens_per_expert"]
        if partial:
            # Held experts are groups 0 .. H in the sorted form; every other pair is
            # of "group H", which has no matrix and no visit, behind them.
            local = experts - held_from
            experts = jnp.where((local >= 0) & (local < n_held), local, n_held)
            sizes = sizes[held_from:held_from + n_held]
        order, inverse = expert_order(experts)
        runs = sorted_runs(experts, n_held, partial)  # where each block of tokens' rows lie
        rows = _gather_rows(tokens, order, inverse, runs, k)  # (T * k, D), expert order
        row_weights = _sort_weights(weights.reshape(-1), order, inverse)  # (T * k,) f32
        # A grouped matmul gives row i to the group the running sum of `sizes`
        # puts it in: a row is processed where that is its own expert.
        group = jnp.searchsorted(jnp.cumsum(sizes), jnp.arange(order.shape[0]), side="right")
        mine = group == experts.reshape(-1)[order]
        aux["held_pairs"] = jnp.sum(sizes)
        if partial:
            held = jnp.arange(order.shape[0]) < aux["held_pairs"]  # by sorted row
            mine = mine & held
            if runs is None:  # the XLA sums read every row, the gradient's too
                rows = _held_rows(rows, held)
        aux["rows_processed"] = jnp.sum(mine)
    with jax.named_scope("experts"):
        gate = grouped_matmul(rows, w_gate.astype(cdt), sizes, short=partial)
        up = grouped_matmul(rows, w_up.astype(cdt), sizes, short=partial)
        if partial:
            # The rows behind the held groups hold what the buffers held (the kernels
            # write nothing there): zeros go on, and zeros come back for the gradients.
            gate, up = _held_rows(gate, held), _held_rows(up, held)
        # The weighting rides in SwiGLU's own pass, in float32, rounded once.
        act = jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32) * row_weights[:, None]
        if partial:
            act = _held_rows(act, held)
        rows = grouped_matmul(act.astype(cdt), w_down.astype(cdt), sizes, short=partial)
        if partial and runs is None:
            rows = _held_rows(rows, held)  # the XLA sum reads every row; the kernel only held ones
    with jax.named_scope("combine"):
        out = _sum_rows(rows, order, inverse, runs, k)
    return out.reshape(B, S, D), aux


def _held_rows(rows, held):
    """`rows` (n, width) where `held` (n,), zeros elsewhere, and the same of
    the gradient: a select both ways, so that nothing a masked row holds,
    finite or not, reaches a sum."""
    return jnp.where(held[:, None], rows, jnp.zeros((), rows.dtype))


def init_moe_params(key, n_layer: int, d_model: int, ff_dim: int, n_experts: int, param_dtype):
    """Stacked per-layer MoE params: router + per-expert SwiGLU weights, no biases."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    std = 0.02
    down_std = std / math.sqrt(2 * n_layer)

    def norm(key, shape, s):
        return (jax.random.normal(key, shape) * s).astype(param_dtype)

    return {
        "router_w": norm(k1, (n_layer, d_model, n_experts), std),
        "w_gate": norm(k2, (n_layer, n_experts, d_model, ff_dim), std),
        "w_up": norm(k3, (n_layer, n_experts, d_model, ff_dim), std),
        "w_down": norm(k4, (n_layer, n_experts, ff_dim, d_model), down_std),
    }


def moe_param_logical_axes() -> Dict[str, Tuple]:
    return {
        "router_w": ("layers", "embed", None),
        "w_gate": ("layers", "expert", "embed", "mlp"),
        "w_up": ("layers", "expert", "embed", "mlp"),
        "w_down": ("layers", "expert", "mlp", "embed"),
    }
