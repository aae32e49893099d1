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

What moves how. Rows (a token's `D` values) move through `_gather_rows` and
`sum_rows`, and through nothing else: where the sorted form is every pair,
XLA's gather by `order // k` (at its floor out of 8,192 tokens) and the
`sum_rows` kernel at 512-row chunks; where it is a prefix of the sort (below),
`sum_rows` at the chunk the held share gives (`ops/sum_rows.py chunk_rows`)
and, out of a source too large for XLA's gather to stay fast (`_rows_by`), the
`gather_rows` kernel, both a block of 128 tokens at a time over the runs the
sort left that block's rows in, so that nothing is copied or written for a
pair held elsewhere beyond the 8-row tiles a run touches. The `tokens * k` scalars of the pairs
(the weights, the sorted expert ids, `order`, `inverse`, the router's picked
scores, the experts' counts) move through sorts and through compares against
an iota of `E`, and nothing gathers or scatters them one element at a time:
XLA's gather and scatter of single 4-byte elements cost the v5e 5-10 ns an
element whatever the array (a `f32[131072]` gather 0.93-1.34 ms, the scatter
that built `inverse` 0.61, the scatter-add of `counts` into 64 bins 1.15),
a sort that carries the same elements along a ninth of that (0.08-0.13 ms),
and a compare of `tokens x k x E` inside a fusion nothing that can be seen
(PERF.md section 6, PR 38: 15.3 of 20.8 ms under `router` and 8.8 of 31.2
under `dispatch` in the LFM2 step were such gathers and scatters). So: the weights and the pairs'
positions ride the one sort by expert as further operands, `inverse` is a
second sort (of `order`, a permutation, with an iota), the weights' gradient
rides a sort keyed by `order` home, and a pick among the `E` columns of a
token, like a count of them, is a sum over a compare. Every one of these moves
values or adds exact zeros: the numbers are those of the gathers, to the bit.
The keys of every sort are all different (`order` is a permutation; the ids
sort with the pair's position as second key), so none is asked to be stable:
XLA takes three times as long to compile a stable sort of this length.

The layer is two halves. `route_and_sort` reads the router's input and makes
the choice and the plan of the sorted form (the sort, its inverse, the runs);
`experts_of` reads the experts' input and that plan and does everything that is
a row long. `moe_mlp` is both on one tensor, which is every model's layer but
one: SmallThinker's router reads the layer's normed input, before attention,
and its experts the state after it (`models/smallthinker.py` calls the halves
apart). The gate's activation is `act`, SiLU unless the model hands another
(ReGLU: `jax.nn.relu`), in the sorted form, its held prefix and that prefix's
hand-written backward rule alike.

Expert weights carry the `expert` logical axis, so a mesh with an `expert`
axis shards them; the sorted form is partitioned by XLA from the sharding
annotations alone (correct on any mesh).

A layer may hold a range of the experts and not all (`moe_mlp(held_from=)`,
the expert weights leading with the held count): one chip's share of an
expert-parallel deployment. The router still scores every expert. The pairs
whose expert lies elsewhere sort behind the held groups, so the held pairs
are a prefix of the sort, and the sorted form is that prefix alone: the first
`held_row_bound` rows (twice what an even router gives the held experts, a
static count) are gathered, multiplied, gated and summed, by the kernels and
by XLA's operations between them alike, and no array is `tokens * k` rows
long but the sort's own index vectors. Of those rows only the owned ones are
written by the gather (and zeros to the next row tile): the grouped matmuls
visit no other, `sum_rows` is pointed at no other, and the selects between
them (`_held_rows`) let nothing else through. A routing that gives this share more
pairs than the bound takes the whole-length form, the same function at
`tokens * k` rows, in which no grouped matmul visits the rows behind the held
groups and `sum_rows` is never pointed at them: `lax.cond` on `held_pairs`
picks, so no pair is ever dropped, and `aux["compact"]` says which ran (neither
branch gathers a scalar: `tests/test_aot_expert_steps.py` counts both). What
the other experts would have added to a token is left out either way: the
layer returns the partial sum that this share computes. The exchange that
would send those pairs to their chips and bring the other chips' partial sums
back does not exist yet (ROADMAP: "an expert exchange across chips").
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.grouped_matmul import grouped_matmul
from ray_tpu.ops.sum_rows import gather_rows, sorted_runs, sum_rows

# Where a layer holds some of the experts, its sorted form is as long as this many times the
# pairs an even router gives them, and the whole-length form takes a routing that gives it
# more. Twice the mean: over the benchmark's seeds a layer of the LFM2 cell read 0.10-0.15 of
# the pairs where the even share is 0.125 (PERF.md section 6, PR 35 and PR 36).
HELD_ROWS_OVER_EVEN = 2
ROW_TILE = 512  # the longest row tile a kernel takes (`grouped_matmul.DRHS_ROW_TILES`)
GATHER_SOURCE_BYTES = 128 * 2 ** 20  # the v5e's VMEM: a prefix out of a source with no room there is the kernel's


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _gather_rows(x, order, inverse, runs, k: int, beside_tokens: bool = False):
    """Row `order[i] // k` of `x` for every i: the tokens in expert order.
    `order` is the sort of the `tokens * k` pairs, or its first rows where
    they hold every pair of a held expert, and `inverse` undoes the whole
    sort, so the gradient sums each token's `k` rows where they lie
    (`_sum_rows`; `runs` says where, `ops/sum_rows.py sorted_runs`), where the
    transpose jax would derive is a scatter-add of `tokens * k` rows.
    `beside_tokens`: `x` is a cotangent of the tokens' size, gathered in a
    backward pass that gathers the tokens too (`_rows_by`)."""
    return _rows_by(x, order, inverse, runs, k, beside_tokens)


def _rows_by(x, order, inverse, runs, k, beside_tokens=False):
    """Which gather runs, read off the shapes. What XLA's gather costs a row
    goes by where its source lies. Out of VMEM (128 MiB on the v5e; XLA's
    memory-space assignment marks the operand `S(1)` and copies it in first)
    it writes 4-5 KB rows at 7-24 ns a row (OLMoE's 65,536 rows out of 8,192
    tokens at 650-730 GB/s, at its floor; SmallThinker's 49,152 out of 16,384 x
    2,560 in 0.39 ms). Out of HBM it pays a copy descriptor's price, 36-41 ns a
    row: a source of 128 MiB, which has no room there (LFM2's), and in a
    backward pass a cotangent of more than half of that, because the layer's
    tokens, as large, already lie in VMEM for the `dispatch` gather made again
    (SmallThinker's 80 MiB: 2.02 ms for 49,152 rows, 0.83 through the kernel;
    PERF.md section 6, PR 72). So a prefix of the sort (`order` shorter than `inverse`: half of its
    rows are nobody's) out of such a source goes through `ops/sum_rows.py
    gather_rows`, the kernel that reads the source once a block of tokens and
    writes the owned rows alone (0.63 ms for 1.19 at LFM2's 32,768 x 2,048;
    0.23 for 0.16 at GLM's 8,192, 0.37 for 0.38 at 16,384: PERF.md section 6,
    PR 40). At exactly half of VMEM no shape tells where XLA leaves a cotangent
    (SDAR's and Keye's in HBM, Trinity-Mini's in VMEM: PERF.md section 7), and
    XLA's gather stays. The kernel leaves the rows behind the owned ones (and
    the zeros to the next row tile) unwritten: a cotangent's go into
    `grouped_matmul`'s backward rule under `short=True` and `_held_rows`'
    selects (`_sorted_form`), which read no such row."""
    source = x.size * x.dtype.itemsize
    no_room = 2 * source > GATHER_SOURCE_BYTES if beside_tokens else source >= GATHER_SOURCE_BYTES
    if order.shape[0] < inverse.shape[0] and no_room:
        return gather_rows(x, order, inverse, runs, k)
    return x[order // k]


def _gather_rows_fwd(x, order, inverse, runs, k, beside_tokens):
    return _rows_by(x, order, inverse, runs, k, beside_tokens), (order, inverse, runs)


def _gather_rows_bwd(k, beside_tokens, res, g):
    return _sum_rows(g, *res, k), None, None, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _sum_rows(rows, order, inverse, runs, k: int):
    """The transpose of `_gather_rows`: each token's `k` rows summed (in
    float32), the sums in token order."""
    return sum_rows(rows, inverse, runs, k)


def _sum_rows_fwd(rows, order, inverse, runs, k):
    return sum_rows(rows, inverse, runs, k), (order, inverse, runs)


def _sum_rows_bwd(k, res, g):
    # The cotangent's gather stands beside the tokens' own, made again for the backward pass.
    return _gather_rows(g, *res, k, True), None, None, None


def _iota(like):
    return jax.lax.iota(jnp.int32, like.shape[0])


def _carried(permutation, values):
    """`values` carried to where a sort of `permutation` puts them: `out[permutation[i]] =
    values[i]`. The keys are all different, so the sort need not be stable (a stable sort of
    131,072 pairs takes XLA three times as long to compile for the v5e: PERF.md, PR 38)."""
    return jax.lax.sort((permutation, values), num_keys=1, is_stable=False)[1]


def _by_expert(ids, weights):
    # A pair's position is the second key: the order of a stable sort by id, from keys that are
    # all different (see `_carried`).
    return tuple(jax.lax.sort((ids, _iota(ids), weights), num_keys=2, is_stable=False))


@jax.custom_vjp
def _sort_weights(ids, weights):
    """The one sort of the `tokens * k` (token, slot) pairs by expert id (and
    by token inside an expert, as a stable sort would leave them): (the ids in
    sorted order; `order`, the pair that each sorted row is; the weights in
    sorted order). The pairs'
    positions and weights ride the sort as further operands, so nothing is
    gathered by `order` afterwards; the weights' gradient rides a sort keyed
    by `order` home (`order` is a permutation: sorting it carries each position
    back to its pair), where jax's own derivative of a many-operand sort
    gathers the tangents by the sorted iota. A caller that takes a prefix of
    the sorted weights hands back zeros behind it: a pair sorted behind the
    prefix gets a zero for its gradient."""
    return _by_expert(ids, weights)


def _sort_weights_fwd(ids, weights):
    out = _by_expert(ids, weights)
    return out, out[1]


def _sort_weights_bwd(order, g):
    return None, _carried(order, g[2])


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
    `norm_topk_prob`, times `scale`. Either way `top_k` gives the choice alone
    (its own instructions are a sort along `E`, 0.16 ms for 32,768 x 64 on the
    v5e); the chosen scores are picked, and the experts' loads counted, by a
    compare of the choice against an iota of `E`, summed.
    `aux` holds the two auxiliary terms of Muennighoff et al. 2024 and the
    load they are computed from (what a caller does not use of it, the
    compiler drops): `load_balance` = E * sum_e f_e * P_e with
    f_e the tokens routed to expert e over the number of tokens (so the f_e
    sum to k) and P_e the mean router probability of e; `z` = mean of
    logsumexp(logits)^2; `tokens_per_expert` (E,) int32."""
    n_experts = router_w.shape[-1]
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router_w.astype(jnp.float32))
    if bias is None:
        scores = choice = jax.nn.softmax(logits, axis=-1)
    else:
        scores = jax.nn.sigmoid(logits)
        choice = scores + bias.astype(jnp.float32)
    _, experts = jax.lax.top_k(jax.lax.stop_gradient(choice), k)
    # A pick among E columns is a compare against an iota inside one fusion, and its gradient the
    # same compare: the token's own score and exact zeros, summed (the module's docstring).
    chosen = experts[..., None] == jnp.arange(n_experts, dtype=experts.dtype)  # (T, k, E)
    weights = jnp.sum(jnp.where(chosen, scores[:, None, :], 0), axis=-1)
    if norm_topk_prob:
        # Without the barrier XLA folds the sum of a token's k weights into the sum over E above,
        # one reduction of k x E picks and zeros that adds the k in another order: a last bit of
        # the weights (with it the LFM2 check's loss is the gathers' to the bit: PERF.md, PR 38).
        weights = jax.lax.optimization_barrier(weights)
        weights = weights / weights.sum(axis=-1, keepdims=True)
    if scale != 1.0:
        weights = weights * scale
    counts = jnp.sum(chosen, axis=(0, 1), dtype=jnp.int32)
    aux = {
        "load_balance": n_experts * jnp.sum(
            counts.astype(jnp.float32) / x.shape[0] * scores.mean(axis=0)),
        "z": jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2),
        "tokens_per_expert": counts,
    }
    return weights, experts, aux


def expert_order(experts, weights):
    """(ids, order, inverse, sorted weights) for `experts` and `weights`
    (T, k): `order` lists the `T * k` (token, slot) pairs by expert (stable, so
    by token inside an expert), `ids` and the sorted weights are the experts
    and the weights in that order, and `inverse` is where each pair went: a
    second sort, of `order` with an iota (`_sort_weights`)."""
    ids, order, weights = _sort_weights(experts.reshape(-1), weights.reshape(-1))
    return ids, order, _carried(order, _iota(order)), weights


def _under_the_current_abstract_mesh(f):
    """`f` traced with the abstract mesh that is current set as such. jax traces
    a `custom_vjp`'s function with none set and its forward rule, under
    `linearize`, with the empty one (or the caller's), and every jit on the way
    down to a kernel keeps a trace for each context: so the forward rule finds
    the traces the function left, and no kernel's body is traced twice (a
    `sum_rows` costs 0.25-0.4 s each time, every run: PERF.md section 6, PR 36)."""
    @functools.wraps(f)
    def traced(*args, **kwargs):
        with jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh()):
            return f(*args, **kwargs)
    return traced


@_under_the_current_abstract_mesh
def route_and_sort(
    tokens,  # (T, D): what the router reads
    router_w,  # (D, E)
    n_held: int,  # the experts the layer holds: E, or H of them from `held_from` on
    *,
    k: int,
    norm_topk_prob: bool = False,
    router_bias=None,  # (E,): `route`'s other scoring
    weight_scale: float = 1.0,
    held_from: int = 0,
) -> Tuple[Tuple, Dict[str, Any]]:
    """The layer's first half, which reads the router's input and no expert's:
    the choice (`route`, scope `router`) and the plan of the sorted form (scope
    `dispatch`: the one sort by expert, its inverse, the runs each block of
    tokens' rows lie in). -> (routing, aux): `routing` is what `experts_of`
    takes, `(sorted weights, sizes, ids, order, inverse, runs)`, and `aux` what
    `route` gives plus `experts` (tokens, k), each token's choices, and
    `held_pairs`. A layer whose router reads another tensor than its experts
    (SmallThinker's: the layer's normed input, before attention) calls the two
    halves itself, the first wherever that tensor is; `moe_mlp` is the two on
    one tensor. Both take the tokens as rows, (T, D)."""
    n_experts = router_w.shape[-1]
    partial = n_held < n_experts
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
        ids, order, inverse, weights = expert_order(experts, weights)
        runs = sorted_runs(experts, n_held, partial)  # where each block of tokens' rows lie
        aux["held_pairs"] = jnp.sum(sizes)
    return (weights, sizes, ids, order, inverse, runs), aux


@_under_the_current_abstract_mesh
def experts_of(
    tokens,  # (T, D) activations, config.dtype: what the experts read
    routing,  # `route_and_sort`'s, of the same T tokens
    w_gate,  # (E, D, F), or (H, D, F) where the layer holds H of the E experts
    w_up,  # likewise
    w_down,  # (E, F, D), or (H, F, D)
    *,
    k: int,
    n_experts: int,  # the router's width
    act: Callable = jax.nn.silu,  # the gate's activation, in float32 (ReGLU: `jax.nn.relu`)
) -> Tuple[Any, Dict[str, Any]]:
    """The layer's second half: `out = sum over the token's k experts of p_e *
    W_down,e (act(W_gate,e h) * W_up,e h)` (T, D) over the experts held, by
    the sorted form or its held prefix, and `rows_processed`, the rows of the
    sorted form that their own expert took, with `compact`, whether the sorted
    form was the held prefix of the sort and not all of it."""
    weights, *plan = routing
    n_held = w_gate.shape[0]
    partial = n_held < n_experts
    pairs = plan[2].shape[0]
    bound = held_row_bound(pairs, n_held, n_experts)
    operands = (tokens, weights, w_gate, w_up, w_down)
    if bound == pairs:
        out, processed = _sorted_form(pairs, k, partial, act, *operands, *plan)
        compact = jnp.zeros((), bool)
    else:
        out, processed, compact = _prefix_or_whole_jit(k, bound, act, operands, tuple(plan))
    return out, {"rows_processed": processed, "compact": compact}


@_under_the_current_abstract_mesh
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
    act: Callable = jax.nn.silu,
) -> Tuple[Any, Dict[str, Any]]:
    """Returns (out (B, S, D), aux): `out = sum over the token's k experts of
    p_e * W_down,e (act(W_gate,e h) * W_up,e h)` (SwiGLU unless the model hands
    another `act`), `aux` as `route` gives it
    plus `experts` (tokens, k), each token's choices, `rows_processed`,
    the rows of the sorted form that their own expert takes, `held_pairs`,
    the (token, expert) pairs whose expert this layer holds, and `compact`,
    whether the sorted form was the held prefix of the sort and not all of it.
    The scopes are read from a device trace by the benchmark's `moe.*_ms`.
    The layer's two halves, `route_and_sort` and `experts_of`, on one tensor.

    Where the expert weights hold fewer experts than the router scores, they
    are experts `held_from .. held_from + H` and the sum runs over those of
    the token's k that are among them (the module's docstring): `out` is this
    share's partial sum."""
    B, S, D = x.shape
    tokens = x.reshape(B * S, D)
    routing, aux = route_and_sort(tokens, router_w, w_gate.shape[0], k=k, norm_topk_prob=norm_topk_prob,
                                  router_bias=router_bias, weight_scale=weight_scale, held_from=held_from)
    out, report = experts_of(tokens, routing, w_gate, w_up, w_down, k=k, n_experts=router_w.shape[-1], act=act)
    return out.reshape(B, S, D), {**aux, **report}


def routing_report(aux: Dict[str, Any], pairs: int) -> Dict[str, Any]:
    """What a router did, for whoever reads it, from `moe_mlp`'s `aux` of a layer (or of a stack of them, on a
    leading axis) and the (token, expert) `pairs` a layer routes: `experts` (tokens, k), each token's choices among
    all the router's experts; `tokens_per_expert` (E,); `load_max_over_mean`; `held_pairs`, the pairs whose expert
    this share holds, and `elsewhere_pairs`, the others; `dropped`, the held pairs less the rows their experts
    processed (`moe_mlp`'s count, made in the form of the layer that ran: the layer is dropless, so 0, counted and
    not assumed); `compact`, whether the layer ran over the prefix of the sort that the held pairs fill and not over
    every pair (`held_row_bound`). A model's `routing_stats` walks its own layers and stacks these."""
    counts = aux["tokens_per_expert"]
    return {
        "experts": aux["experts"],
        "tokens_per_expert": counts,
        "load_max_over_mean": counts.max(axis=-1) / counts.mean(axis=-1),
        "held_pairs": aux["held_pairs"],
        "elsewhere_pairs": pairs - aux["held_pairs"],
        "dropped": aux["held_pairs"] - aux["rows_processed"],
        "compact": aux["compact"],
    }


def swiglu(x, w_gate, w_up, w_down, act: Callable = jax.nn.silu):
    """`W_down (act(W_gate x) * W_up x)` of x (B, S, D) with (D, F), (D, F),
    (F, D), SwiGLU unless the model hands another `act`: operands in x's dtype,
    the gate in float32, rounded once."""
    cdt = x.dtype
    gate = jnp.einsum("bsd,df->bsf", x, w_gate.astype(cdt))
    up = jnp.einsum("bsd,df->bsf", x, w_up.astype(cdt))
    hidden = (act(gate.astype(jnp.float32)) * up.astype(jnp.float32)).astype(cdt)
    return jnp.einsum("bsf,fd->bsd", hidden, w_down.astype(cdt))


def shared_expert(x, w_gate, w_up, w_down):
    """The expert every token meets, beside the routed ones: one `swiglu`
    outside the sort, with no router and no weight. A chip that holds a share
    of the routed experts computes it whole, for its own tokens: across the
    shares of a layer it is counted once, not once a share. The scope is read
    from a device trace by the benchmark's `moe.shared_ms`."""
    with jax.named_scope("shared_expert"):
        return swiglu(x, w_gate, w_up, w_down)


def held_row_bound(pairs: int, n_held: int, n_experts: int) -> int:
    """The rows of the sorted form where a layer holds `n_held` of `n_experts`
    experts: `HELD_ROWS_OVER_EVEN` times the held experts' even share of the
    `pairs`, in whole `ROW_TILE`s, and no more than `pairs`."""
    even = pairs * n_held / n_experts
    return min(pairs, math.ceil(HELD_ROWS_OVER_EVEN * even / ROW_TILE) * ROW_TILE)


def _sorted_form(n: int, k: int, partial: bool, act: Callable, tokens, weights, w_gate, w_up, w_down,
                 sizes, ids, order, inverse, runs):
    """Everything of the layer that is as long as the sorted form, over its first
    `n` rows: (the tokens' sums (T, D), the rows that their own expert took).
    `n` is every pair, or with `partial` at least the held pairs, which the sort
    put first. `weights` and `ids` are in sorted order (`expert_order`); `act`
    is the gate's activation (`experts_of`)."""
    cdt = tokens.dtype
    with jax.named_scope("dispatch"):
        order = order[:n]
        rows = _gather_rows(tokens, order, inverse, runs, k)  # (n, D), expert order
        row_weights = weights[:n]  # (n,) f32; its gradient is zeros behind the prefix
        # A grouped matmul gives row i to the group the running sum of `sizes`
        # puts it in: a row is processed where that is its own expert.
        group = jnp.searchsorted(jnp.cumsum(sizes), jnp.arange(n), side="right", method="compare_all")
        mine = group == ids[:n]
        if partial:
            held = jnp.arange(n) < jnp.sum(sizes)  # by sorted row
            mine = mine & held
            if runs is None:  # the XLA sums read every row, the gradient's too
                rows = _held_rows(rows, held)
        processed = jnp.sum(mine)
    with jax.named_scope("experts"):
        gate = grouped_matmul(rows, w_gate.astype(cdt), sizes, short=partial)
        up = grouped_matmul(rows, w_up.astype(cdt), sizes, short=partial)
        if partial:
            # The rows behind the held groups hold what the buffers held (the kernels
            # write nothing there): zeros go on, and zeros come back for the gradients.
            gate, up = _held_rows(gate, held), _held_rows(up, held)
        # The weighting rides in SwiGLU's own pass, in float32, rounded once.
        hidden = act(gate.astype(jnp.float32)) * up.astype(jnp.float32) * row_weights[:, None]
        if partial:
            hidden = _held_rows(hidden, held)
        rows = grouped_matmul(hidden.astype(cdt), w_down.astype(cdt), sizes, short=partial)
        if partial and runs is None:
            rows = _held_rows(rows, held)  # the XLA sum reads every row; the kernel only held ones
    with jax.named_scope("combine"):
        out = _sum_rows(rows, order, inverse, runs, k)
    return out, processed


def _either_form(bound: int, routing, branch, *operands):
    """(whether the first `bound` sorted rows hold every held pair, `branch(n)`
    of the operands for n those rows if so, else for every pair)."""
    sizes, _, order, _, _ = routing
    compact = jnp.sum(sizes) <= bound
    return compact, jax.lax.cond(compact, branch(bound), branch(order.shape[0]), *operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _prefix_or_whole(k: int, bound: int, act: Callable, operands, routing):
    """`_sorted_form` over the first `bound` rows where they hold every held
    pair, else over every pair: both dropless, the same function of a row
    count; with the count of processed rows, whether it was the prefix.
    Differentiated as a whole, so that what a branch keeps for its backward
    pass lives inside the branch: through a plain `lax.cond` each branch also
    returns the other's residuals, as zeros, and the forward pass keeps both
    sets (the LFM2 step then wants 12.6 GB of temporaries for 10.3, and does
    not compile for a v5e: PERF.md section 6, PR 36). The backward pass makes
    the branch's forward pass again instead."""
    return _prefix_or_whole_fwd(k, bound, act, operands, routing)[0]


def _prefix_or_whole_fwd(k, bound, act, operands, routing):
    def forward(n):
        return lambda operands, routing: _sorted_form(n, k, True, act, *operands, *routing)

    compact, (out, processed) = _either_form(bound, routing, forward, operands, routing)
    return (out, processed, compact), (operands, routing)


def _prefix_or_whole_bwd(k, bound, act, res, g):
    operands, routing = res

    def backward(n):
        def branch(operands, routing, g):
            def forward(*operands):
                # `jax.vjp` wraps the first scope opened under it (`jvp(sorted_form)`,
                # `transpose(jvp(sorted_form))`): the layer's own stay whole path components.
                with jax.named_scope("sorted_form"):
                    return _sorted_form(n, k, True, act, *operands, *routing)[0]

            return jax.vjp(forward, *operands)[1](g)
        return branch

    return _either_form(bound, routing, backward, operands, routing, g[0])[1], None


_prefix_or_whole.defvjp(_prefix_or_whole_fwd, _prefix_or_whole_bwd)
# A function of its own in the program, as `sum_rows._pallas_sum_rows`: a model's layers of one
# shape trace both forms and their backward passes once, and lower their kernels once.
_prefix_or_whole_jit = jax.jit(_prefix_or_whole, static_argnums=(0, 1, 2))


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
