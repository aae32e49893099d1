"""LFM2-MoE for the benchmark: the system under test built through ray_tpu's
public API, a plain float32 reference written from the source's equations, the
comparison that decides `correct`, and the arithmetic of FLOPs and bytes.

A configuration file (`benchmark/configs/<name>.json`) with `"model": "lfm2"`
is served by this module. Keys read, under the names of the source's
`config.json`: `layer_types`, `num_dense_layers`, `hidden_size`,
`intermediate_size` (the dense SwiGLU), `moe_intermediate_size` (one expert),
`num_attention_heads`, `num_key_value_heads`, `num_experts` (the experts held
here; the router's width is `published.num_experts` where the file cuts the
key), `num_experts_per_tok`, `norm_topk_prob`, `routed_scaling_factor`,
`use_expert_bias`, `conv_L_cache`, `conv_bias`, `vocab_size`,
`max_position_embeddings`, `norm_eps`, `rope_parameters.rope_theta`; and the
benchmark's own: `first_expert_held`, `dtype`, `param_dtype`, `remat_policy`,
`attention`, `learning_rate` (the peak), `warmup_steps` and `total_steps` (the
schedule `default_optimizer` makes of them; constant where they are absent).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

from benchmark.models import gpt2

CONV, ATTENTION = "conv", "full_attention"

# ------------------------------------------------------------------ arithmetic
# No jax below this line until `build`: the parent and the tests use these.
# Everything counts what this chip computes: the experts it holds, the slice
# of the vocabulary it holds, the layers it holds.


def router_width(c: Dict[str, Any]) -> int:
    """The experts the router scores: the published count where the file's
    `num_experts` is the chip's share of them."""
    return c.get("published", {}).get("num_experts", c["num_experts"])


def _layers(c: Dict[str, Any]) -> Dict[str, int]:
    types = c["layer_types"]
    return {"conv": types.count(CONV), "attention": types.count(ATTENTION),
            "dense": c["num_dense_layers"], "moe": len(types) - c["num_dense_layers"]}


def held_pairs_per_layer(c: Dict[str, Any], tokens: int) -> float:
    """The (token, expert) pairs an even router gives the experts held here."""
    return tokens * c["num_experts_per_tok"] * c["num_experts"] / router_width(c)


def active_matmul_params(c: Dict[str, Any]) -> float:
    """Parameters one token meets here as an operand of a matrix
    multiplication: a conv layer's in and out projections (4 d^2), an
    attention layer's q, k, v and output projections, the dense SwiGLU's three
    matrices, the router, the three matrices of each expert a token's pairs
    meet on this chip (`num_experts_per_tok` x held / routed over, in
    expectation), and the tied head over the vocabulary's slice. The
    embedding is a lookup; norms and the convolution's taps multiply nothing
    on the MXU."""
    d, n = c["hidden_size"], _layers(c)
    kv = d * c["num_key_value_heads"] // c["num_attention_heads"]
    pairs_here = held_pairs_per_layer(c, 1)
    return (n["conv"] * 4 * d * d + n["attention"] * (2 * d * d + 2 * d * kv)
            + n["dense"] * 3 * d * c["intermediate_size"]
            + n["moe"] * (d * router_width(c) + pairs_here * 3 * d * c["moe_intermediate_size"])
            + c["vocab_size"] * d)


def train_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    """FLOPs the forward and backward passes require per token on this chip: 6
    per active matmul parameter, plus attention over the full square of `seq`
    positions in the attention layers (12 * d * seq each, the convention
    `gpt2.train_flops_per_token` has). Recomputation is not counted."""
    return 6.0 * active_matmul_params(c) + 12.0 * _layers(c)["attention"] * c["hidden_size"] * seq


def _attention_as_gpt2(c: Dict[str, Any]) -> Dict[str, Any]:
    # k and v are repeated to the query heads before the kernel: it reads 32 heads of each.
    return {"n_embd": c["hidden_size"], "n_head": c["num_attention_heads"],
            "n_layer": _layers(c)["attention"]}


def flash_flops_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """`gpt2.flash_flops_per_step` at this configuration's query heads, for its
    attention layers alone."""
    return gpt2.flash_flops_per_step(_attention_as_gpt2(c), rows, seq)


def flash_bytes_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    return gpt2.flash_bytes_per_step(_attention_as_gpt2(c), rows, seq)


def moe_expert_flops_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """FLOPs the held experts of one train step require: each pair an even
    router gives them meets three matrices of hidden_size x
    moe_intermediate_size, 2 FLOPs a parameter forward and 4 backward. The
    pairs of experts held elsewhere are not this chip's and are not counted."""
    pairs = held_pairs_per_layer(c, rows * seq)
    return 6.0 * 3 * c["hidden_size"] * c["moe_intermediate_size"] * pairs * _layers(c)["moe"]


def moe_expert_bytes_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """Bytes the nine grouped products of a step must move in bf16: each of the
    three matmuls reads its held rows and every held expert's matrix and
    writes its result, once forward and once for each of its two gradients."""
    pairs = held_pairs_per_layer(c, rows * seq)
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    one_product = pairs * d + c["num_experts"] * d * f + pairs * f
    return 2.0 * 3 * 3 * one_product * _layers(c)["moe"]


def conv_mix_bytes_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """Bytes the scope `conv_mix` of one train step must move in bf16 (gate
    `B * u`, three taps, gate `C * c`: no matmul, so bandwidth is its only
    bound): forward it reads the in-projection's three (tokens, hidden) parts
    and writes one; backward it reads the three and the result's gradient and
    writes the three's gradients: 11 such arrays a conv layer. The four of a
    recomputation are not counted: the compiled step has run none under this
    scope since PR 36 (no operation there carries `rematted_computation`;
    PERF.md section 5, PR 50), and a floor that held them would let a kernel
    at HBM's rate on the passes that exist read over 100 %. The taps' own
    (3, hidden) are not counted."""
    return 11.0 * rows * seq * c["hidden_size"] * 2 * _layers(c)["conv"]


# ---------------------------------------------------------------------- system
def lfm2_config(c: Dict[str, Any]):
    import jax.numpy as jnp

    from ray_tpu.models.lfm2 import LFM2Config

    assert c["conv_bias"] is False and c["use_expert_bias"] is True, "the only form written"
    return LFM2Config(
        vocab_size=c["vocab_size"], layer_types=tuple(c["layer_types"]),
        n_dense_layers=c["num_dense_layers"], n_head=c["num_attention_heads"],
        n_kv_head=c["num_key_value_heads"], d_model=c["hidden_size"], d_ff=c["intermediate_size"],
        d_expert=c["moe_intermediate_size"], n_experts=router_width(c),
        experts_per_token=c["num_experts_per_tok"], n_experts_held=c["num_experts"],
        first_expert_held=c.get("first_expert_held", 0), norm_topk_prob=c["norm_topk_prob"],
        routed_scaling_factor=float(c["routed_scaling_factor"]), conv_kernel=c["conv_L_cache"],
        max_seq_len=c["max_position_embeddings"],
        rope_theta=float(c["rope_parameters"]["rope_theta"]), norm_eps=c["norm_eps"],
        dtype=jnp.dtype(c["dtype"]), param_dtype=jnp.dtype(c["param_dtype"]),
        remat_policy=c["remat_policy"], attention=c["attention"],
    )


class System:
    """cfg, optimizer, state and jitted step, made as a user makes them."""

    def __init__(self, c: Dict[str, Any], mesh, seed: int):
        import jax

        from ray_tpu.models import create_train_state, default_optimizer, make_train_step

        self.c = c
        self.mesh = mesh
        self.cfg = lfm2_config(c)
        self.optimizer = default_optimizer(
            learning_rate=c["learning_rate"], warmup_steps=c.get("warmup_steps", 0),
            total_steps=c.get("total_steps", 0))
        self.state = create_train_state(self.cfg, jax.random.PRNGKey(seed), self.optimizer, mesh=mesh)
        self.step = make_train_step(self.cfg, self.optimizer, mesh=mesh)

    def attention_path(self, rows_per_device: int, seq: int, platform: str) -> str:
        from ray_tpu.ops.flash_attention import select_backend

        return select_backend((rows_per_device, self.cfg.n_head, seq, self.cfg.head_dim), platform)


def build(c: Dict[str, Any], mesh, seed: int) -> System:
    return System(c, mesh, seed)


# ------------------------------------------------------------------- reference
def rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def qk_norm(q, k, q_scale, k_scale, eps):
    """q (..., heads, head_dim) and k are normed per head: over the head's own
    head_dim, with one learned scale of that width shared by the heads."""
    return rms_norm(q, q_scale, eps), rms_norm(k, k_scale, eps)


def short_conv_mix(b, c, u, taps):
    """`C * conv(B * u)` for b, c, u (batch, seq, channels) and `taps`
    (kernel, channels): depthwise and causal, tap j on position t - (kernel -
    1) + j, zeros before the row's first position, no bias. Written as the
    sum over the shifted copies."""
    import jax.numpy as jnp

    z = b * u
    kernel, seq = taps.shape[0], z.shape[1]
    mixed = jnp.zeros_like(z)
    for j in range(kernel):
        back = kernel - 1 - j
        shifted = jnp.concatenate([jnp.zeros_like(z[:, :back]), z[:, :seq - back]], axis=1)
        mixed = mixed + taps[j] * shifted
    return c * mixed


def routing_matrix(scores, bias, k: int, renormalise: bool, scale: float):
    """(tokens, experts): the sigmoid score where the expert is one of the k
    largest of `score + bias`, zero elsewhere; the bias enters the choice
    only. Renormalised over the chosen with the source's `norm_topk_prob`,
    then times `routed_scaling_factor`."""
    import jax
    import jax.numpy as jnp

    chosen = jax.nn.one_hot(jax.lax.top_k(scores + bias, k)[1], scores.shape[-1], dtype=bool).any(axis=1)
    weights = jnp.where(chosen, scores, 0.0)
    if renormalise:
        weights = weights / weights.sum(-1, keepdims=True)
    return weights * scale, chosen


def layers_in_order(blocks, c: Dict[str, Any]) -> List[Any]:
    """(operator, feed-forward, the layer's own parameters) of every layer in
    the published order, out of the tree the system trains: the leading
    (dense) layers one tree each, then one stack for every place in the
    period (the same place of every period on its leading axis), then any
    trailing layers one tree each."""
    import jax

    types, dense = c["layer_types"], c["num_dense_layers"]
    n_periods = jax.tree.leaves(blocks["period"])[0].shape[0] if blocks["period"] else 0
    own = list(blocks["leading"]) + [
        jax.tree.map(lambda a, p=p: a[p], place) for p in range(n_periods) for place in blocks["period"]
    ] + list(blocks["trailing"])
    assert len(own) == len(types), (len(own), len(types))
    return [(op, "dense" if i < dense else "moe", layer) for i, (op, layer) in enumerate(zip(types, own))]


def reference_loss(params, tokens, c: Dict[str, Any], dtype=None):
    """LFM2-MoE (the `lfm2_moe` model code of the source, as far as its
    `config.json` and the issue's equations say) in float32 `jax.numpy`;
    returns (loss, chosen) with `chosen` (expert layers, tokens, experts) the
    experts each token was given, among all the router scores.

    Pre-norm block, RMSNorm, no bias anywhere: `h = x + Op(norm(x))`, `y = h +
    FFN(norm(h))`. `Op` of a `conv` layer: `(B, C, u) = split3(W_in n)`, `Op =
    W_out (C * conv(B * u))` (`short_conv_mix`). `Op` of a `full_attention`
    layer: q, k, v projections, q and k RMS-normed per head (`qk_norm`), rotary
    embedding on halves of head_dim (`rotate_half`), k and v repeated to the
    query heads, causal softmax at head_dim^-1/2, `W_o`. `FFN` of the first
    `num_dense_layers`: `W2 (silu(W1 n) * W3 n)`. Of the others: `s =
    sigmoid(W_r n)`, the `num_experts_per_tok` largest of `s + expert_bias`,
    weights `s` at the chosen over their sum (`routing_matrix`), `sum_e w_e
    W2_e (silu(W1_e n) * W3_e n)` over the experts this chip holds: the
    weights of the chosen experts that it does not hold are dropped with
    their experts, and that partial sum goes on, as in the system. Final
    RMSNorm, the tied embedding as head, mean cross entropy of the next
    token. No kernel, no sort, no grouped matmul, no bf16: every held expert
    is applied to every token and weighted by the routing matrix, which is
    zero where the expert was not chosen.

    Takes the parameter tree the system trains (`layers_in_order`; heads as a
    separate axis, the conv's taps as (kernel, channels), its in-projection's
    three parts side by side) and reads it as the published shapes.
    Departures from a line-by-line transcription, none changes the arithmetic:
    each layer, and inside it each group of query heads that share a key/value
    head and each expert, is recomputed in the backward pass
    (`jax.checkpoint`), so that neither the 4096 x 4096 scores of all 32 heads
    nor every expert's activations for all tokens are held at once beside the
    training state; the renormalisation divides by the plain sum (the
    source's code, as remembered, adds 1e-6 to it: 5e-7 of four sigmoid
    scores); the source applies `expert_bias` as a buffer that its trainer
    moves, and here it is constant.

    `dtype` (default float32) computes everything, parameters and logits
    included, in that type instead: what a lower precision than the
    configuration states would give, for PERF.md's second reading.
    """
    import jax
    import jax.numpy as jnp

    f = jnp.dtype(dtype or jnp.float32)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    batch, seq = inputs.shape
    d, n_head, n_kv = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    head_dim, group = d // n_head, n_head // n_kv
    eps, k = c["norm_eps"], c["num_experts_per_tok"]
    held, first = c["num_experts"], c.get("first_expert_held", 0)

    theta = c["rope_parameters"]["rope_theta"]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None]
    angles = jnp.concatenate([angles, angles], axis=-1)
    cos, sin = jnp.cos(angles).astype(f), jnp.sin(angles).astype(f)
    mask = jnp.tril(jnp.ones((seq, seq), bool))

    def rotate_half(x):
        return jnp.concatenate([-x[..., head_dim // 2:], x[..., :head_dim // 2]], axis=-1)

    def rope(x):  # (batch, seq, heads, head_dim)
        return x * cos[:, None, :] + rotate_half(x) * sin[:, None, :]

    @jax.checkpoint
    def heads_of_one_kv(q, kk, v):
        """q (batch, group, seq, head_dim) against one key/value head (batch, seq, head_dim)."""
        scores = jnp.einsum("bgqh,bkh->bgqk", q, kk) / jnp.sqrt(jnp.asarray(head_dim, f))
        scores = jnp.where(mask, scores, -jnp.inf)
        return jnp.einsum("bgqk,bkh->bgqh", jax.nn.softmax(scores, axis=-1), v)

    def attention(h, layer):
        q = (h @ layer["wq"].reshape(d, n_head * head_dim)).reshape(batch, seq, n_head, head_dim)
        kk = (h @ layer["wk"].reshape(d, n_kv * head_dim)).reshape(batch, seq, n_kv, head_dim)
        v = (h @ layer["wv"].reshape(d, n_kv * head_dim)).reshape(batch, seq, n_kv, head_dim)
        q, kk = qk_norm(q, kk, layer["q_norm"], layer["k_norm"], eps)
        q, kk = rope(q), rope(kk)
        # Query heads g * group .. (g + 1) * group read key/value head g (`repeat_kv`).
        q = q.reshape(batch, seq, n_kv, group, head_dim).transpose(2, 0, 3, 1, 4)
        out = jax.lax.map(lambda xs: heads_of_one_kv(*xs),
                          (q, kk.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)))
        out = out.transpose(1, 3, 0, 2, 4).reshape(batch, seq, n_head * head_dim)
        return out @ layer["wo"].reshape(n_head * head_dim, d)

    def short_conv(h, layer):
        b, cc, u = jnp.split(h @ layer["conv_in"], 3, axis=-1)
        return short_conv_mix(b, cc, u, layer["conv_w"]) @ layer["conv_out"]

    @jax.checkpoint
    def expert(h, weight, w_gate, w_up, w_down):
        return weight[:, None] * ((jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down)

    def experts(h, moe):
        h = h.reshape(batch * seq, d)
        scores = jax.nn.sigmoid(h @ moe["router_w"])
        weights, chosen = routing_matrix(scores, moe["expert_bias"], k, c["norm_topk_prob"],
                                         c["routed_scaling_factor"])

        def add_expert(y, xs):
            weight, w_gate, w_up, w_down = xs
            return y + expert(h, weight, w_gate, w_up, w_down), None

        y, _ = jax.lax.scan(add_expert, jnp.zeros_like(h),
                            (weights.T[first:first + held], moe["w_gate"], moe["w_up"], moe["w_down"]))
        return y.reshape(batch, seq, d), chosen

    def block(op, ffn):
        @jax.checkpoint
        def apply(x, layer):
            layer = jax.tree.map(lambda p: p.astype(f), layer)
            h = rms_norm(x, layer["op_norm"], eps)
            x = x + (short_conv(h, layer) if op == CONV else attention(h, layer))
            h = rms_norm(x, layer["ffn_norm"], eps)
            if ffn == "dense":
                return x + (jax.nn.silu(h @ layer["w_gate"]) * (h @ layer["w_up"])) @ layer["w_down"], None
            y, chosen = experts(h, layer["moe"])
            return x + y, chosen
        return apply

    with jax.default_matmul_precision("highest"):
        embed = params["embed"].astype(f)
        x = embed[inputs]
        chosen = []
        for op, ffn, layer in layers_in_order(params["blocks"], c):
            x, of_layer = block(op, ffn)(x, layer)
            if of_layer is not None:
                chosen.append(of_layer)
        x = rms_norm(x, params["final_norm"].astype(f), eps)
        logits = x @ embed.T
        logp = jax.nn.log_softmax(logits, axis=-1)
        loss = -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()
        return loss.astype(jnp.float32), jnp.stack(chosen)


# Tolerances of the agreement between the system (bf16 activations and matmul
# operands, the Pallas kernels, grouped matmuls over the held groups of the
# sorted rows; f32 router, norms, logits and parameters) and the reference (f32
# throughout, every held expert on every token), at seeded initial weights, on
# the two rows (8,192 tokens) of the run's first batch that the harness hands
# `check`. Measured on the chip at the published widths (PR 35, PERF.md section
# 6; 18 readings of the system, each its own seed; 3 of the reference itself
# with parameters, router, norms and logits in bf16, the nearest precision
# below the configuration's):
#   loss            system off by 1.1e-5..4.6e-4; the bf16 reference by 9.6e-3,
#                   1.78e-2, 1.78e-2 (its loss is one of 9.375, 9.4375, 9.5: values
#                   near 9.4 carry 8 bits, so by luck it can land anywhere within
#                   3e-2 of the f32 loss, on it too: the loss cannot be the limit
#                   that always tells)
#   gradient norm   system 7.8e-5..1.7e-4; the bf16 reference 2.8e-4..3.2e-4: it
#                   cannot tell the precision
#   flipped choices system 1.54..1.69 % of the 131,072 (token, slot) choices of
#                   the four expert layers; the bf16 reference 2.22..2.27 %
# So the share of flipped choices is the limit that tells the precision: 1.95 %
# is 0.26 above the system's largest reading (its readings lie within 0.15 of
# each other) and 0.27 below the bf16 reference's smallest. It is three times
# OLMoE's share because four sigmoid scores of 64 lie closer than eight softmax
# probabilities: where the bf16 block hands the f32 router a slightly different
# input, a token whose 4th and 5th scores (plus bias) are closer than that
# difference picks another expert. The loss bound is five times the largest
# reading and a quarter of the bf16 reference's smallest. The gradient norm's
# bound, twelve times the largest reading, is there for another function: a
# reference that renormalises over all scores, lets the selection bias into
# the weights, norms q and k over the whole projection or moves the
# convolution's taps differs by 7e-3 to 1.0 at trained weights
# (`tests/test_lfm2.py`). No comparison of losses can see parameters kept in
# bf16: the parameters' and the optimizer moments' dtype is checked by name.
LOSS_ABS_TOL = 2.5e-3
GRAD_NORM_REL_TOL = 2e-3
FLIPPED_SHARE_TOL = 1.95e-2


def _issued_rows(held_sizes) -> int:
    """Rows of products the grouped-matmul kernels issue for these groups, a
    layer's three products forward and for both gradients."""
    from ray_tpu.ops import grouped_matmul as gm

    return sum(3 * (2 * gm.issued_rows(sizes, gm.SUB_ROWS) + gm.issued_rows(sizes, gm.DRHS_SUB_ROWS))
               for sizes in held_sizes)


def check(system: System, tokens, *, loss_tol: float = LOSS_ABS_TOL,
          grad_tol: float = GRAD_NORM_REL_TOL, flipped_tol: float = FLIPPED_SHARE_TOL
          ) -> Dict[str, Any]:
    """Loss and global gradient norm of the system's `loss_fn` (through the
    attention path, the short convolutions and the held-experts layer it
    selects) against the reference's, on `tokens` (a jax array, already
    placed) with the run's own parameters; what the routers did
    (`routing_stats`: `dropped` must be 0), and the share of (token, slot)
    choices on which system and reference pick different experts. Two
    programs, one after the other, so that the two gradient trees (1.9 GB each
    at the published widths) are never held at once."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import lfm2

    cfg, mesh, c = system.cfg, system.mesh, system.c
    params = system.state.params

    def of_system(params, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: lfm2.loss_fn(p, {"tokens": tokens}, cfg, mesh=mesh))(params)
        return loss, optax.global_norm(grads), lfm2.routing_stats(params, tokens[:, :-1], cfg)

    def of_reference(params, tokens, experts):
        (loss, chosen), grads = jax.value_and_grad(
            lambda p: reference_loss(p, tokens, c), has_aux=True)(params)
        # experts (layers, tokens, k): is each of the system's choices one of the reference's?
        same = jnp.take_along_axis(chosen, experts, axis=-1)
        return loss, optax.global_norm(grads), 1.0 - same.mean()

    sys_loss, sys_norm, stats = jax.jit(of_system)(params, tokens)
    ref_loss, ref_norm, flipped = jax.jit(of_reference)(params, tokens, stats.pop("experts"))
    got = [float(x) for x in (sys_loss, sys_norm, ref_loss, ref_norm)]
    sys_loss, sys_norm, ref_loss, ref_norm = got
    want_dtype = jnp.dtype(c["param_dtype"])
    leaves = jax.tree.leaves(params) + [
        x for x in jax.tree.leaves(system.state.opt_state) if getattr(x, "ndim", 0) > 0]
    wrong_dtype = sorted({str(x.dtype) for x in leaves if x.dtype != want_dtype})
    stats = jax.device_get(stats)
    per_expert = stats["tokens_per_expert"]
    held, elsewhere = int(stats["held_pairs"].sum()), int(stats["elsewhere_pairs"].sum())
    first = c.get("first_expert_held", 0)
    held_sizes = [[int(x) for x in layer[first:first + c["num_experts"]]] for layer in per_expert]
    out = {
        "loss_system": sys_loss, "loss_reference": ref_loss,
        "grad_norm_system": sys_norm, "grad_norm_reference": ref_norm,
        "loss_abs_err": abs(sys_loss - ref_loss),
        "grad_norm_rel_err": abs(sys_norm - ref_norm) / max(ref_norm, 1e-30),
        "expert_choices_flipped_share": float(flipped),
        "state_dtypes_other_than_stated": wrong_dtype,
        "routing": {
            "pairs_per_layer": int(per_expert[0].sum()),
            "held_pairs": held,
            "elsewhere_pairs": elsewhere,
            "held_pairs_share": held / max(held + elsewhere, 1),
            "held_pairs_per_layer": [int(x) for x in stats["held_pairs"]],
            "held_tokens_per_expert": held_sizes,
            "issued_over_held": _issued_rows(held_sizes) / max(9 * held, 1),
            "dropped": int(stats["dropped"].sum()),
            "load_max_over_mean": float(stats["load_max_over_mean"].max()),
            "tokens_per_expert_min": int(per_expert.min()),
            "tokens_per_expert_max": int(per_expert.max()),
        },
    }
    out["ok"] = bool(
        all(map(math.isfinite, got)) and out["loss_abs_err"] <= loss_tol
        and out["grad_norm_rel_err"] <= grad_tol and not wrong_dtype
        and out["routing"]["dropped"] == 0
        and out["expert_choices_flipped_share"] <= flipped_tol)
    return out
