"""GLM-4 MoE Lite (GLM-4.7-Flash) for the benchmark: the system under test built
through ray_tpu's public API, a plain float32 reference written from the
issue's equations, the comparison that decides `correct`, and the arithmetic
of FLOPs and bytes.

A configuration file (`benchmark/configs/<name>.json`) with `"model":
"glm4_moe_lite"` is served by this module. Keys read, under the names of the
source's `config.json`: `num_hidden_layers`, `first_k_dense_replace`,
`hidden_size`, `intermediate_size` (the dense SwiGLU), `moe_intermediate_size`
(one expert), `num_attention_heads`, `q_lora_rank`, `kv_lora_rank`,
`qk_nope_head_dim`, `qk_rope_head_dim`, `v_head_dim`, `n_routed_experts` (the
experts held here; the router's width is `published.n_routed_experts` where
the file cuts the key), `n_shared_experts`, `num_experts_per_tok`,
`norm_topk_prob`, `routed_scaling_factor`, `num_nextn_predict_layers`,
`vocab_size`, `max_position_embeddings`, `rms_norm_eps`, `rope_theta`; and the
benchmark's own: `first_expert_held`, `mtp_loss_weight`, `dtype`,
`param_dtype`, `remat_policy`, `attention`, `learning_rate` (the peak),
`warmup_steps` and `total_steps` (the schedule `default_optimizer` makes of
them; constant where they are absent).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

from benchmark.models import gpt2
from benchmark.models.lfm2 import _issued_rows, rms_norm, routing_matrix  # noqa: F401  (the same norm, routing and kernels)

# ------------------------------------------------------------------ arithmetic
# No jax below this line until `build`: the parent and the tests use these.
# Everything counts what this chip computes: the routed experts it holds, the
# shared expert whole, the slice of the vocabulary it holds, the layers it
# holds, and the prediction module where the configuration has one.


def router_width(c: Dict[str, Any]) -> int:
    """The experts the router scores: the published count where the file's
    `n_routed_experts` is the chip's share of them."""
    return c.get("published", {}).get("n_routed_experts", c["n_routed_experts"])


def _layers(c: Dict[str, Any]) -> Dict[str, int]:
    """Layers by what they hold, the prediction module's among them."""
    dense, module = c["first_k_dense_replace"], c["num_nextn_predict_layers"]
    moe = c["num_hidden_layers"] - dense + module
    return {"dense": dense, "moe": moe, "attention": dense + moe, "module": module}


def head_dim(c: Dict[str, Any]) -> int:
    return c["qk_nope_head_dim"] + c["qk_rope_head_dim"]


def attention_matmul_params(c: Dict[str, Any]) -> int:
    """One latent-attention layer's five matrices: both down-projections, both
    up-projections, the output projection."""
    d, nh = c["hidden_size"], c["num_attention_heads"]
    return (d * c["q_lora_rank"] + c["q_lora_rank"] * nh * head_dim(c)
            + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + c["kv_lora_rank"] * nh * (c["qk_nope_head_dim"] + c["v_head_dim"])
            + nh * c["v_head_dim"] * d)


def held_pairs_per_layer(c: Dict[str, Any], tokens: int) -> float:
    """The (token, expert) pairs an even router gives the experts held here."""
    return tokens * c["num_experts_per_tok"] * c["n_routed_experts"] / router_width(c)


def num_params(c: Dict[str, Any]) -> int:
    """Every parameter this chip holds, by hand: per layer the attention's five
    matrices and four norms (two of hidden_size, one each of the two latents);
    the dense SwiGLU's three; an expert layer's router, selection bias, shared
    expert and held routed experts; the embedding, the final norm and the
    head (untied); the module's two norms, its projection of 2 x hidden_size,
    its expert layer and its own final norm."""
    d, n = c["hidden_size"], _layers(c)
    expert = 3 * d * c["moe_intermediate_size"]
    attention = attention_matmul_params(c) + 2 * d + c["q_lora_rank"] + c["kv_lora_rank"]
    moe = d * router_width(c) + router_width(c) + (c["n_shared_experts"] + c["n_routed_experts"]) * expert
    return (2 * c["vocab_size"] * d + d + n["attention"] * attention
            + n["dense"] * 3 * d * c["intermediate_size"] + n["moe"] * moe
            + n["module"] * (2 * d * d + 3 * d))


def active_matmul_params(c: Dict[str, Any]) -> float:
    """Parameters one token meets here as an operand of a matrix
    multiplication: each layer's attention matrices, the dense SwiGLU, the
    router, the shared expert, the three matrices of each routed expert a
    token's pairs meet on this chip (`num_experts_per_tok` x held / routed
    over, in expectation), the head over the vocabulary's slice, and for the
    prediction module its projection and the head once more. The embedding is
    a lookup; norms multiply nothing on the MXU."""
    d, n = c["hidden_size"], _layers(c)
    expert = 3 * d * c["moe_intermediate_size"]
    moe = d * router_width(c) + (c["n_shared_experts"] + held_pairs_per_layer(c, 1)) * expert
    return (n["attention"] * attention_matmul_params(c) + n["dense"] * 3 * d * c["intermediate_size"]
            + n["moe"] * moe + (1 + n["module"]) * c["vocab_size"] * d + n["module"] * 2 * d * d)


def train_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    """FLOPs the forward and backward passes require per token on this chip: 6
    per active matmul parameter, plus attention over the full square of `seq`
    positions at the heads' full width in every attention call (12 * heads *
    head_dim * seq each, the convention `gpt2.train_flops_per_token` has).
    Recomputation is not counted."""
    return (6.0 * active_matmul_params(c)
            + 12.0 * _layers(c)["attention"] * c["num_attention_heads"] * head_dim(c) * seq)


def _attention_as_gpt2(c: Dict[str, Any]) -> Dict[str, Any]:
    # The kernels see 20 heads of 256 (q . k over 192 + 64, p . v over 256) in every attention
    # call of the step: the layers' and the prediction module's.
    return {"n_embd": c["num_attention_heads"] * head_dim(c), "n_head": c["num_attention_heads"],
            "n_layer": _layers(c)["attention"]}


def flash_flops_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """`gpt2.flash_flops_per_step` at this configuration's heads (q . k at 256,
    p . v at 256, causal half), for every attention call of the step."""
    assert head_dim(c) == c["v_head_dim"]
    return gpt2.flash_flops_per_step(_attention_as_gpt2(c), rows, seq)


def flash_bytes_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    return gpt2.flash_bytes_per_step(_attention_as_gpt2(c), rows, seq)


def moe_expert_flops_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """FLOPs the held routed experts of one train step require: each pair an
    even router gives them meets three matrices of hidden_size x
    moe_intermediate_size, 2 FLOPs a parameter forward and 4 backward. The
    pairs of experts held elsewhere are not this chip's, and the shared expert
    is no grouped product (`moe.shared_ms` reads it): neither is counted."""
    pairs = held_pairs_per_layer(c, rows * seq)
    return 6.0 * 3 * c["hidden_size"] * c["moe_intermediate_size"] * pairs * _layers(c)["moe"]


def moe_expert_bytes_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """Bytes the nine grouped products of a step must move in bf16: each of the
    three matmuls reads its held rows and every held expert's matrix and
    writes its result, once forward and once for each of its two gradients."""
    pairs = held_pairs_per_layer(c, rows * seq)
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    one_product = pairs * d + c["n_routed_experts"] * d * f + pairs * f
    return 2.0 * 3 * 3 * one_product * _layers(c)["moe"]


# ---------------------------------------------------------------------- system
def model_config(c: Dict[str, Any]):
    import jax.numpy as jnp

    from ray_tpu.models.glm4_moe_lite import GLM4MoELiteConfig

    assert c["topk_method"] == "noaux_tc" and c["n_group"] == c["topk_group"] == 1, "the only routing written"
    assert not c["attention_bias"] and not c["tie_word_embeddings"] and c["rope_scaling"] is None
    assert c["hidden_act"] == "silu" and c["num_key_value_heads"] == c["num_attention_heads"]
    return GLM4MoELiteConfig(
        vocab_size=c["vocab_size"], n_layer=c["num_hidden_layers"],
        n_dense_layers=c["first_k_dense_replace"], n_head=c["num_attention_heads"],
        d_model=c["hidden_size"], q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"], qk_rope_head_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"], d_ff=c["intermediate_size"], d_expert=c["moe_intermediate_size"],
        n_experts=router_width(c), experts_per_token=c["num_experts_per_tok"],
        n_shared_experts=c["n_shared_experts"], n_experts_held=c["n_routed_experts"],
        first_expert_held=c.get("first_expert_held", 0), norm_topk_prob=c["norm_topk_prob"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        n_predict_layers=c["num_nextn_predict_layers"], mtp_loss_weight=float(c["mtp_loss_weight"]),
        max_seq_len=c["max_position_embeddings"], rope_theta=float(c["rope_theta"]),
        norm_eps=c["rms_norm_eps"], dtype=jnp.dtype(c["dtype"]), param_dtype=jnp.dtype(c["param_dtype"]),
        remat_policy=c["remat_policy"], attention=c["attention"],
    )


class System:
    """cfg, optimizer, state and jitted step, made as a user makes them."""

    def __init__(self, c: Dict[str, Any], mesh, seed: int):
        import jax

        from ray_tpu.models import create_train_state, default_optimizer, make_train_step

        self.c = c
        self.mesh = mesh
        self.cfg = model_config(c)
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
def layers_in_order(params, c: Dict[str, Any]) -> List[Any]:
    """Every layer's own parameters in the published order, out of the tree the
    system trains: the leading (dense) layers one tree each, then the expert
    layers off their stack's leading axis."""
    import jax

    (stack,) = params["blocks"]["period"]
    n_moe = c["num_hidden_layers"] - c["first_k_dense_replace"]
    assert jax.tree.leaves(stack)[0].shape[0] == n_moe and not params["blocks"]["trailing"]
    return list(params["blocks"]["leading"]) + [jax.tree.map(lambda a, i=i: a[i], stack) for i in range(n_moe)]


def latent_attention(h, layer, c: Dict[str, Any], cos, sin):
    """Latent attention on the normed h (batch, seq, hidden), before `W_o`:
    (batch, seq, heads * v_head_dim). `c_q = N(h W_qa)`, `q = c_q W_qb` in
    heads of (192 | 64); `[c_kv | k_r] = h W_kva`, `c_kv = N(c_kv)`, `[k_n |
    v] = c_kv W_kvb` in heads of (192 | 256); the 64 rotated on halves
    (`rotate_half`), the one `k_r` shared by every head; causal softmax at
    (192 + 64)^-1/2. One head at a time, recomputed in the backward pass, so
    that the 4096 x 4096 scores of 20 heads are never held at once."""
    import jax
    import jax.numpy as jnp

    batch, seq, _ = h.shape
    nh, nope, rope = c["num_attention_heads"], c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    kvl, eps = c["kv_lora_rank"], c["rms_norm_eps"]
    mask = jnp.tril(jnp.ones((seq, seq), bool))

    def rotated(x):  # (..., seq, rope)
        half = jnp.concatenate([-x[..., rope // 2:], x[..., :rope // 2]], axis=-1)
        return x * cos + half * sin

    c_q = rms_norm(h @ layer["wq_a"], layer["q_a_norm"], eps)
    q = jnp.einsum("bsr,rnh->nbsh", c_q, layer["wq_b"])  # (heads, batch, seq, 192 + 64)
    kv = h @ layer["wkv_a"]
    c_kv, k_r = rms_norm(kv[..., :kvl], layer["kv_a_norm"], eps), rotated(kv[..., kvl:])
    k_n_v = jnp.einsum("bsr,rnh->nbsh", c_kv, layer["wkv_b"])  # (heads, batch, seq, 192 + 256)

    @jax.checkpoint
    def one_head(q, k_n_v):
        q = jnp.concatenate([q[..., :nope], rotated(q[..., nope:])], axis=-1)
        k = jnp.concatenate([k_n_v[..., :nope], k_r], axis=-1)
        scores = jnp.einsum("bqh,bkh->bqk", q, k) / jnp.sqrt(jnp.asarray(nope + rope, q.dtype))
        scores = jnp.where(mask, scores, -jnp.inf)
        return jnp.einsum("bqk,bkh->bqh", jax.nn.softmax(scores, axis=-1), k_n_v[..., nope:])

    out = jax.lax.map(lambda xs: one_head(*xs), (q, k_n_v))  # (heads, batch, seq, 256)
    return out.transpose(1, 2, 0, 3).reshape(batch, seq, nh * c["v_head_dim"])


def reference_logits(params, tokens, c: Dict[str, Any], dtype=None):
    """GLM-4 MoE Lite (as far as the source's `config.json` and the issue's
    equations say) in float32 `jax.numpy` on `tokens` (batch, seq + 1):
    (the next-token loss, the prediction module's loss or None, the logits of
    the model's head, the module's logits or None, `chosen` (expert layers,
    tokens, experts): the experts each token was given among all the router
    scores, the module's layer last).

    Pre-norm block, RMSNorm, no bias anywhere: `h = x + W_o attn(N(x))`
    (`latent_attention`), `y = h + FFN(N(h))`. `FFN` of the first
    `first_k_dense_replace` layers: `W2 (silu(W1 n) * W3 n)`. Of the others:
    `s = sigmoid(W_r n)`, the `num_experts_per_tok` largest of `s +
    expert_bias`, weights `s` at the chosen over their sum times
    `routed_scaling_factor` (`routing_matrix`), `sum_e w_e E_e(n)` over the
    experts this chip holds plus the shared expert `E_s(n)` whole: the weights
    of the chosen experts that it does not hold are dropped with their
    experts, and that partial sum goes on, as in the system. Final RMSNorm, the
    head (untied), mean cross entropy of the next token. The prediction
    module: `g_i = W_eh [N_h(x^L_i) ; N_e(Emb(t_{i+1}))]` with `x^L` the last
    layer's output before the final norm, one expert layer as above on `g`, a
    norm of its own, the same head; it predicts `t_{i+2}`, each row's last
    position left out of its mean. No kernel, no sort, no grouped matmul, no
    bf16: every held expert is applied to every token and weighted by the
    routing matrix, which is zero where the expert was not chosen.

    Takes the parameter tree the system trains (`layers_in_order`; heads as a
    separate axis) and reads it as the published shapes. Departures from a
    line-by-line transcription, none changes the arithmetic: each layer, each
    head and each expert is recomputed in the backward pass
    (`jax.checkpoint`), as is each head's product over the vocabulary; the
    renormalisation divides by the plain sum (DeepSeek-V3's code adds 1e-20);
    the source applies `expert_bias` as a buffer that its trainer moves, and
    here it is constant.

    `dtype` (default float32) computes everything up to and including the
    logits, parameters too, in that type instead, and the cross entropy of
    those logits in float32 as always: what a lower precision than the
    configuration states would give, for PERF.md's second reading
    (`tools/glm_readings.py`).
    """
    import jax
    import jax.numpy as jnp

    f = jnp.dtype(dtype or jnp.float32)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    batch, seq = inputs.shape
    d, eps, k = c["hidden_size"], c["rms_norm_eps"], c["num_experts_per_tok"]
    held, first = c["n_routed_experts"], c.get("first_expert_held", 0)

    rope = c["qk_rope_head_dim"]
    inv_freq = 1.0 / (c["rope_theta"] ** (jnp.arange(0, rope, 2, dtype=jnp.float32) / rope))
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None]
    angles = jnp.concatenate([angles, angles], axis=-1)
    cos, sin = jnp.cos(angles).astype(f), jnp.sin(angles).astype(f)

    def swiglu(h, w_gate, w_up, w_down):
        return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down

    @jax.checkpoint
    def expert(h, weight, w_gate, w_up, w_down):
        return weight[:, None] * swiglu(h, w_gate, w_up, w_down)

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
        y = y + swiglu(h, moe["shared_gate"], moe["shared_up"], moe["shared_down"])
        return y.reshape(batch, seq, d), chosen

    @jax.checkpoint
    def block(x, layer):
        layer = jax.tree.map(lambda p: p.astype(f), layer)
        h = rms_norm(x, layer["attn_norm"], eps)
        x = x + latent_attention(h, layer, c, cos, sin) @ layer["wo"].reshape(-1, d)
        h = rms_norm(x, layer["ffn_norm"], eps)
        if "moe" not in layer:
            return x + swiglu(h, layer["w_gate"], layer["w_up"], layer["w_down"]), None
        y, chosen = experts(h, layer["moe"])
        return x + y, chosen

    @jax.checkpoint
    def head(x, scale, table, targets, mask):
        logits = rms_norm(x, scale.astype(f), eps) @ table.T
        # The cross entropy and its mean are float32 whatever `dtype`: a program with bf16
        # logits would still add 8,192 terms in float32 (in bf16 the mean is one of few values).
        log_p = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(log_p, targets[..., None], axis=-1)[..., 0]
        return jnp.where(mask, nll, 0.0).sum() / mask.sum(), logits

    with jax.default_matmul_precision("highest"):
        embed, table = params["embed"].astype(f), params["lm_head"].astype(f)
        x = embed[inputs]
        chosen = []
        for layer in layers_in_order(params, c):
            x, of_layer = block(x, layer)
            if of_layer is not None:
                chosen.append(of_layer)
        everywhere = jnp.ones((batch, seq), bool)
        loss, logits = head(x, params["final_norm"], table, targets, everywhere)
        module_loss = module_logits = None
        if c["num_nextn_predict_layers"]:
            mtp = jax.tree.map(lambda p: p.astype(f), params["mtp"])
            both = jnp.concatenate([rms_norm(x, mtp["h_norm"], eps),
                                    rms_norm(embed[targets], mtp["e_norm"], eps)], axis=-1)
            g, of_layer = block(both @ mtp["eh_proj"], params["mtp"]["block"])
            chosen.append(of_layer)
            after = jnp.concatenate([targets[:, 1:], targets[:, :1]], axis=1)
            module_loss, module_logits = head(
                g, mtp["norm"], table, after, everywhere & (jnp.arange(seq) < seq - 1))
        return loss, module_loss, logits, module_logits, jnp.stack(chosen)


def reference_loss(params, tokens, c: Dict[str, Any], dtype=None):
    """(loss, chosen) of `reference_logits`: the next-token cross entropy plus
    `mtp_loss_weight` times the prediction module's, where there is one."""
    loss, module_loss, _, _, chosen = reference_logits(params, tokens, c, dtype)
    if module_loss is not None:
        loss = loss + c["mtp_loss_weight"] * module_loss
    return loss, chosen


# Tolerances of the agreement between the system (bf16 activations and matmul
# operands, the Pallas kernels, grouped matmuls over the held groups of the
# sorted rows; f32 router, norms, logits and parameters) and the reference (f32
# throughout, every held expert on every token), at seeded initial weights, on
# the two rows (8,192 tokens) of the run's first batch that the harness hands
# `check`: here the whole batch. Measured on the chip at the published widths
# (`tools/glm_readings.py`; PR 39, PERF.md section 6; 15 readings of the system,
# each its own seed; 4 of the reference itself with parameters, router, norms
# and logits in bf16, the nearest precision below the configuration's, the
# cross entropy of those logits summed in f32 as a program would):
#   loss            system off by 3.8e-5..7.5e-4; the bf16 reference by
#                   1.3e-4..9.1e-4: it cannot tell the precision. (With the
#                   mean taken in bf16 too the reference read 8.6e-3..3.3e-2
#                   off, which was the rounding of one scalar near 13.4.)
#   gradient norm   system 7.7e-5..4.7e-4; the bf16 reference 3.7e-4..5.8e-4:
#                   it cannot tell the precision either
#   flipped choices system 1.08..1.20 % of the 163,840 (token, slot) choices of
#                   the five expert layers; the bf16 reference 1.75..1.95 %
#                   (seven readings: how the mean is taken does not reach it)
# So one limit tells the precision, the share of flipped choices: 1.5 % is
# 0.30 above the system's largest reading (its readings lie within 0.12 of
# each other) and 0.25 below the bf16 reference's smallest. The loss bound is
# the LFM2 cell's, 3.3 times the system's largest reading; with the gradient
# norm's, four times the largest reading, it is there for another function: a
# reference with no shared expert, weights not scaled by 1.8, the module's
# halves swapped or its loss at another weight differs by far more at trained
# weights (`tests/test_glm4_moe_lite.py`). No comparison of losses can see
# parameters kept in bf16: the parameters' and the optimizer moments' dtype is
# checked by name.
LOSS_ABS_TOL = 2.5e-3
GRAD_NORM_REL_TOL = 2e-3
FLIPPED_SHARE_TOL = 1.5e-2


def check(system: System, tokens, *, loss_tol: float = LOSS_ABS_TOL,
          grad_tol: float = GRAD_NORM_REL_TOL, flipped_tol: float = FLIPPED_SHARE_TOL
          ) -> Dict[str, Any]:
    """Loss and global gradient norm of the system's `loss_fn` (through the
    attention path, the latent projections, the held-experts layer, the shared
    expert and the prediction module it selects) against the reference's, on
    `tokens` (a jax array, already placed) with the run's own parameters; what
    the routers did (`routing_stats`: `dropped` must be 0), and the share of
    (token, slot) choices on which system and reference pick different
    experts. Two programs, one after the other, so that the two gradient trees
    (2.8 GB each at the published widths) are never held at once."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import glm4_moe_lite as model

    cfg, mesh, c = system.cfg, system.mesh, system.c
    params = system.state.params

    def of_system(params, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: model.loss_fn(p, {"tokens": tokens}, cfg, mesh=mesh))(params)
        return loss, optax.global_norm(grads), model.routing_stats(params, tokens, cfg)

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
    held_sizes = [[int(x) for x in layer[first:first + c["n_routed_experts"]]] for layer in per_expert]
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
            "compact_layers": int(stats["compact"].sum()),
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
