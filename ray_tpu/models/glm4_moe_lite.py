"""GLM-4 MoE Lite (zai-org, `model_type` `glm4_moe_lite`; the published sizes
are GLM-4.7-Flash's, 30B-A3B): a pre-norm stack in which every layer's
attention is latent attention (DeepSeek-V2's MLA), the first
`n_dense_layers` feed forward through one wide SwiGLU and every later layer
through a token-choice mixture of SwiGLU experts beside one shared expert that
every token meets, and a multi-token-prediction module gives the step a second
head and a second loss.

    block:   h = x + W_o attn(N(x));  y = h + FFN(N(h))
    latent:  c_q = N(h W_qa) (768);  q = c_q W_qb -> heads x (192 | 64)
             [c_kv | k_r] = h W_kva (512 | 64);  c_kv = N(c_kv)
             [k_n | v] = c_kv W_kvb -> heads x (192 | 256)
             q = [q_n, rope(q_r)], k = [k_n, rope(k_r)], the one k_r for every
             head; causal softmax at 256^-1/2; W_o (heads x 256 -> d)
    experts: s = sigmoid(W_r n); the 4 largest of s + b; weights s at the
             chosen over their sum, times `routed_scaling_factor`; plus the
             shared expert, a SwiGLU of `n_shared_experts * d_expert`
    module:  g_i = W_eh [N_h(x^L_i) ; N_e(Emb(t_{i+1}))], one expert layer on
             g, a norm of its own, the model's head: it predicts t_{i+2}.
             loss = CE + `mtp_loss_weight` * CE_module (DeepSeek-V3's form)

Built from what the zoo has: RMSNorm and the rotary tables are `llama.py`'s,
the patterned stack (a leading dense layer, then a scan over the expert
layers; remat, attention dispatch, head, loss) is `stack.py`'s, the routed
experts are `moe.moe_mlp`, told which experts this chip holds
(`n_experts_held`), and the shared expert is `moe.shared_expert`, computed
whole on every chip for its own tokens. The rotary halves are split, not
interleaved (a permutation of `W_qb`'s and `W_kva`'s rotary columns away from
the source; weights here are random). There is no auxiliary loss: the source
balances by moving `expert_bias` outside the loss (`topk_method` `noaux_tc`);
that rule is not in its `config.json`, and here the bias is a seeded buffer
that no optimizer step changes (`frozen_params`). Key and value heads are both
256 wide here; `xing4.py` runs this attention with keys of 192 and values of 128.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import apply_rope, rms_norm, rope_tables
from ray_tpu.models.moe import moe_mlp, routing_report, shared_expert, swiglu
from ray_tpu.models.stack import (Pattern, apply_stack, block, causal_lm_loss, draw, draw_layer, lm_head, lm_loss,
                                  lm_tree, per_leaf)

DENSE, MOE = "latent_dense", "latent_moe"


@dataclasses.dataclass(frozen=True)
class GLM4MoELiteConfig:
    """Defaults are GLM-4.7-Flash's published sizes (the source's key where the name differs)."""

    vocab_size: int = 154880
    n_layer: int = 47  # num_hidden_layers
    n_dense_layers: int = 1  # first_k_dense_replace: the first layers' feed-forward is one dense SwiGLU
    n_head: int = 20
    d_model: int = 2048
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    d_ff: int = 10240  # the dense SwiGLU (`intermediate_size`)
    d_expert: int = 1536  # one expert (`moe_intermediate_size`)
    n_experts: int = 64  # the router's width (`n_routed_experts`)
    experts_per_token: int = 4
    n_shared_experts: int = 1  # one SwiGLU of n_shared_experts * d_expert beside the routed ones
    n_experts_held: Optional[int] = None  # routed experts computed here (None: all), ...
    first_expert_held: int = 0  # ... from this one on
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.8
    n_predict_layers: int = 1  # num_nextn_predict_layers: 0 is the model without the module
    mtp_loss_weight: float = 0.3  # DeepSeek-V3's first 10 T tokens; config.json has no key
    max_seq_len: int = 202752
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    remat_policy: Optional[str] = "save_attn"  # as LlamaConfig's: q, k, v, o at full width are what is kept
    attention: str = "auto"  # auto | flash | xla

    def __post_init__(self):
        assert self.n_predict_layers in (0, 1), "one prediction module is the only depth written"

    @property
    def head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def held(self) -> int:
        return self.n_experts if self.n_experts_held is None else self.n_experts_held

    @classmethod
    def nano(cls, **kw):
        """Tiny config for CPU tests: one dense and two expert layers, 8 experts
        of which this share holds 2, 2 a token, one prediction module."""
        kw.setdefault("vocab_size", 256)
        kw.setdefault("max_seq_len", 64)
        kw.setdefault("n_experts_held", 2)
        kw.setdefault("first_expert_held", 2)
        kw.setdefault("n_layer", 3)
        return cls(n_dense_layers=1, n_head=4, d_model=64, q_lora_rank=32, kv_lora_rank=16,
                   qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=32, d_ff=160, d_expert=32,
                   n_experts=8, experts_per_token=2, **kw)


def layer_kinds(config: GLM4MoELiteConfig) -> Tuple[str, ...]:
    return (DENSE,) * config.n_dense_layers + (MOE,) * (config.n_layer - config.n_dense_layers)


# --------------------------------------------------------------------------- sizes
def _attention_matmul_params(config: GLM4MoELiteConfig) -> int:
    d, nh = config.d_model, config.n_head
    return (d * config.q_lora_rank + config.q_lora_rank * nh * config.head_dim
            + d * (config.kv_lora_rank + config.qk_rope_head_dim)
            + config.kv_lora_rank * nh * (config.qk_nope_head_dim + config.v_head_dim)
            + nh * config.v_head_dim * d)


def _kind_params(config: GLM4MoELiteConfig, kind: str) -> Dict[str, int]:
    """Parameters of one layer of `kind`: `matmul` that every token meets as an
    operand of a product, `experts` in all the routed experts held here, `other`."""
    d = config.d_model
    matmul = _attention_matmul_params(config)
    other = 2 * d + config.q_lora_rank + config.kv_lora_rank  # four norms
    experts = 0
    if kind == DENSE:
        matmul += 3 * d * config.d_ff
    else:
        matmul += d * config.n_experts + 3 * d * config.n_shared_experts * config.d_expert
        other += config.n_experts  # expert_bias
        experts = 3 * config.held * d * config.d_expert
    return {"matmul": matmul, "experts": experts, "other": other}


def num_params(config: GLM4MoELiteConfig) -> int:
    """Of this share: the experts held, not all the router names; the embedding
    and the head (untied), and the prediction module where there is one."""
    d = config.d_model
    n = 2 * config.vocab_size * d + d + sum(
        sum(_kind_params(config, kind).values()) for kind in layer_kinds(config))
    if config.n_predict_layers:
        n += 2 * d * d + 3 * d + sum(_kind_params(config, MOE).values())
    return n


def train_flops_per_token(config: GLM4MoELiteConfig, seq_len: int) -> float:
    """6 FLOPs per matmul parameter a token meets here (of its
    `experts_per_token` experts the share `held / n_experts` that this chip
    computes, in expectation; the head once more for the prediction module)
    plus full-square attention in every attention call, q . k at the keys'
    width and p . v at the values', as `gpt.py` counts where they are one."""
    per_expert = 3 * config.d_model * config.d_expert
    pairs_here = config.experts_per_token * config.held / config.n_experts
    moe = _kind_params(config, MOE)["matmul"] + pairs_here * per_expert
    head = config.vocab_size * config.d_model
    active = head + config.n_dense_layers * _kind_params(config, DENSE)["matmul"] + (
        config.n_layer - config.n_dense_layers) * moe
    calls = config.n_layer
    if config.n_predict_layers:
        active += 2 * config.d_model * config.d_model + moe + head
        calls += 1
    return 6.0 * active + 6.0 * calls * config.n_head * (config.head_dim + config.v_head_dim) * seq_len


# --------------------------------------------------------------------------- init
def _layer_shapes(config: GLM4MoELiteConfig, kind: str):
    """{name: (shape, init: a normal's std or "ones", logical axes)} of one layer of `kind`."""
    d, nh, f = config.d_model, config.n_head, config.d_expert
    ql, kvl, rope = config.q_lora_rank, config.kv_lora_rank, config.qk_rope_head_dim
    std, out_std = 0.02, 0.02 / math.sqrt(2 * config.n_layer)
    shapes: Dict[str, Any] = {
        "attn_norm": ((d,), "ones", (None,)), "ffn_norm": ((d,), "ones", (None,)),
        "wq_a": ((d, ql), std, ("embed", None)), "q_a_norm": ((ql,), "ones", (None,)),
        "wq_b": ((ql, nh, config.head_dim), std, (None, "heads", None)),
        # Columns: the latent, then the one rotary key every head shares.
        "wkv_a": ((d, kvl + rope), std, ("embed", None)), "kv_a_norm": ((kvl,), "ones", (None,)),
        # Per head: the key's part that takes no rotation, then the value.
        "wkv_b": ((kvl, nh, config.qk_nope_head_dim + config.v_head_dim), std, (None, "heads", None)),
        "wo": ((nh, config.v_head_dim, d), out_std, ("heads", None, "embed")),
    }
    if kind == DENSE:
        shapes.update({
            "w_gate": ((d, config.d_ff), std, ("embed", "mlp")),
            "w_up": ((d, config.d_ff), std, ("embed", "mlp")),
            "w_down": ((config.d_ff, d), out_std, ("mlp", "embed")),
        })
    else:
        held, fs = config.held, config.n_shared_experts * f
        shapes["moe"] = {
            "router_w": ((d, config.n_experts), std, ("embed", None)),
            "expert_bias": ((config.n_experts,), std, (None,)),
            "w_gate": ((held, d, f), std, ("expert", "embed", "mlp")),
            "w_up": ((held, d, f), std, ("expert", "embed", "mlp")),
            "w_down": ((held, f, d), out_std, ("expert", "mlp", "embed")),
            "shared_gate": ((d, fs), std, ("embed", "mlp")),
            "shared_up": ((d, fs), std, ("embed", "mlp")),
            "shared_down": ((fs, d), out_std, ("mlp", "embed")),
        }
    return shapes


def _tree(config: GLM4MoELiteConfig, leaf: Callable, layers: Optional[Callable] = None,
          layer_shapes: Callable = _layer_shapes):
    """`stack.lm_tree` of this model, a tree like the parameters' (`leaf(name,
    shape, init, axes)` for every leaf, a layer's through `layers(kind, i,
    stack)` where `init_params` brings it), and the prediction module beside
    it, its layer as layer `n_layer`. `layer_shapes`: a model's own table of a
    layer's leaves where it adds some to this one's (`xing4.py`)."""
    d, n_moe = config.d_model, config.n_layer - config.n_dense_layers
    shapes = functools.partial(layer_shapes, config)
    layers = layers or (lambda kind, i, stack: per_leaf(shapes(kind), leaf, stack))  # the prediction module's too
    norm = lambda name: leaf(name, (d,), "ones", (None,))  # noqa: E731
    tree = lm_tree(config, ((DENSE,) * config.n_dense_layers, (MOE,), n_moe, ()), shapes, leaf, layers, head="lm_head")
    if config.n_predict_layers:
        tree["mtp"] = {
            "h_norm": norm("h_norm"), "e_norm": norm("e_norm"),
            # Rows: the last layer's activations, then the next token's embedding.
            "eh_proj": leaf("eh_proj", (2 * d, d), 0.02, (None, "embed")),
            "block": layers(MOE, config.n_layer, ()),
            "norm": norm("norm"),
        }
    return tree


def init_params(config: GLM4MoELiteConfig, key, layer_shapes: Callable = _layer_shapes) -> Dict[str, Any]:
    pd = config.param_dtype
    k_leaves, k_layers = jax.random.split(key)
    keys = (jax.random.fold_in(k_leaves, n) for n in itertools.count())  # one a leaf, in `_tree`'s order
    return _tree(
        config,
        lambda name, shape, init, axes: draw(next(keys), shape, init, pd),
        lambda kind, i, stack: draw_layer(jax.random.fold_in(k_layers, i), layer_shapes(config, kind), stack, pd),
        layer_shapes)


def param_logical_axes(config: GLM4MoELiteConfig, layer_shapes: Callable = _layer_shapes) -> Dict[str, Any]:
    return _tree(config, lambda name, shape, init, axes: axes, layer_shapes=layer_shapes)


def frozen_params(config: GLM4MoELiteConfig, layer_shapes: Callable = _layer_shapes) -> Dict[str, Any]:
    """True at the leaves that are buffers and no parameters (`expert_bias`):
    `make_train_step` applies no update to them, weight decay included."""
    return _tree(config, lambda name, shape, init, axes: name == "expert_bias", layer_shapes=layer_shapes)


# --------------------------------------------------------------------------- forward
# A block's four sublayers as functions of the sublayer's input alone, with no residual sum: this model adds each to
# its one stream, `xing4.py` hands each to a mix of four (`ops/hyper_connections.py`). `config` is either model's.
def latent_qkv(x, layer, config, cos, sin):
    """Latent attention up to the kernel on x (B, S, D), not yet normed: its norm, both down-projections, their
    norms, both up-projections, the rotation and the broadcast of the shared rotary key: q, k (B, heads, S, nope +
    rope) and v (B, heads, S, `v_head_dim`). The up-projections' columns are sliced on the weights, so no activation
    is split off a lane boundary."""
    cdt, eps = config.dtype, config.norm_eps
    nope, kvl = config.qk_nope_head_dim, config.kv_lora_rank
    h = rms_norm(x, layer["attn_norm"], eps).astype(cdt)
    c_q = jnp.einsum("bsd,dr->bsr", h, layer["wq_a"].astype(cdt))
    c_q = rms_norm(c_q, layer["q_a_norm"], eps).astype(cdt)
    wq_b = layer["wq_b"].astype(cdt)
    q_n = jnp.einsum("bsr,rnh->bnsh", c_q, wq_b[..., :nope])
    q_r = jnp.einsum("bsr,rnh->bnsh", c_q, wq_b[..., nope:])
    kv = jnp.einsum("bsd,dr->bsr", h, layer["wkv_a"].astype(cdt))
    c_kv = rms_norm(kv[..., :kvl], layer["kv_a_norm"], eps).astype(cdt)
    k_r = apply_rope(kv[:, None, :, kvl:], cos, sin)  # (B, 1, S, rope): one for all heads
    wkv_b = layer["wkv_b"].astype(cdt)
    k_n = jnp.einsum("bsr,rnh->bnsh", c_kv, wkv_b[..., :nope])
    v = jnp.einsum("bsr,rnh->bnsh", c_kv, wkv_b[..., nope:])
    q = jnp.concatenate([q_n, apply_rope(q_r, cos, sin)], axis=-1)
    k = jnp.concatenate([k_n, jnp.broadcast_to(k_r, q_r.shape)], axis=-1)
    return q, k, v


def attention_out(o, layer, config):
    """W_o on the heads' output o (B, heads, S, `v_head_dim`): (B, S, D)."""
    return jnp.einsum("bnsh,nhd->bsd", o.astype(config.dtype), layer["wo"].astype(config.dtype))


def dense_ffn(x, layer, config):
    """The leading layers' feed-forward on x (B, S, D), not yet normed: its norm and one SwiGLU of `d_ff`."""
    h = rms_norm(x, layer["ffn_norm"], config.norm_eps).astype(config.dtype)
    return swiglu(h, layer["w_gate"], layer["w_up"], layer["w_down"])


def moe_ffn(x, layer, config):
    """An expert layer's feed-forward on x (B, S, D), not yet normed: (the held routed experts' part, the shared
    expert's, what `moe_mlp` reports of the routing)."""
    h = rms_norm(x, layer["ffn_norm"], config.norm_eps).astype(config.dtype)
    moe = layer["moe"]
    routed, aux = moe_mlp(
        h, moe["router_w"], moe["w_gate"], moe["w_up"], moe["w_down"],
        k=config.experts_per_token, norm_topk_prob=config.norm_topk_prob,
        router_bias=moe["expert_bias"], weight_scale=config.routed_scaling_factor,
        held_from=config.first_expert_held)
    shared = shared_expert(h, moe["shared_gate"], moe["shared_up"], moe["shared_down"])
    return routed, shared, aux


def _kinds(config: GLM4MoELiteConfig, stats: bool = False):
    """`stack.Pattern.kinds`: the parts of each kind of layer. x: (B, S, D);
    cos/sin: this rank's rows of the rotary tables (over `qk_rope_head_dim`).
    An `out_part` returns (x, aux): a zero, or with `stats` what `moe_mlp`
    reports of the layer (nothing for a dense one). The scope names are read
    from the compiled program's `op_name`s (PERF.md, "names")."""

    def qkv_part(x, layer, cos, sin):
        with jax.named_scope("mla_latent"):
            return latent_qkv(x, layer, config, cos, sin)

    def dense(x, layer):
        with jax.named_scope("dense_mlp"):
            return x + dense_ffn(x, layer, config), None

    def moe(x, layer):
        with jax.named_scope("moe"):
            routed, shared, aux = moe_ffn(x, layer, config)
            return x + routed + shared, aux

    def out_part(ffn):
        def part(x, o, layer, rng):
            del rng  # no dropout
            with jax.named_scope("attn_out"):
                x = x + attention_out(o, layer, config)
            x, aux = ffn(x, layer)
            return x, aux if stats else jnp.zeros((), jnp.float32)
        return part

    return {DENSE: (qkv_part, out_part(dense)), MOE: (qkv_part, out_part(moe))}


def pattern(config: GLM4MoELiteConfig, stats: bool = False) -> Pattern:
    return Pattern(_kinds(config, stats), (MOE,), config.n_layer - config.n_dense_layers,
                   (DENSE,) * config.n_dense_layers)


def _streams(seq_len: int, config: GLM4MoELiteConfig):
    return rope_tables(seq_len, config.qk_rope_head_dim, config.rope_theta)


def mtp_input(params, x, next_tokens, config: GLM4MoELiteConfig):
    """The prediction module's input (B, S, D): the last layer's activations x
    (before the final norm) and the embedding of each position's next token,
    each normed, side by side through `eh_proj`."""
    mtp, cdt, eps = params["mtp"], config.dtype, config.norm_eps
    with jax.named_scope("embed"):
        e = params["embed"].astype(cdt)[next_tokens]
    both = jnp.concatenate([rms_norm(x, mtp["h_norm"], eps), rms_norm(e, mtp["e_norm"], eps)], axis=-1)
    return jnp.einsum("bse,ed->bsd", both.astype(cdt), mtp["eh_proj"].astype(cdt))


def mtp_logits(params, x, next_tokens, config: GLM4MoELiteConfig, attention_fn=None, mesh=None):
    """Logits (B, S, vocab) f32 of the prediction module for the token after
    `next_tokens` (B, S), from the last layer's activations x: its one expert
    layer through `stack.block` (the same remat, the same scopes), a norm of
    its own, the model's head."""
    g = mtp_input(params, x, next_tokens, config)
    with jax.named_scope("blocks"):
        g, _ = block(g, params["mtp"]["block"], config, *pattern(config).kinds[MOE], attention_fn, mesh,
                     _streams(x.shape[1], config))
    return lm_head(g, lambda g: rms_norm(g, params["mtp"]["norm"], config.norm_eps),
                   params["lm_head"], config.dtype)


def hidden(params, tokens, config: GLM4MoELiteConfig, attention_fn=None, mesh=None,
           num_microbatches: Optional[int] = None):
    """The last layer's activations (B, S, D) for `tokens` (B, S), before the final norm."""
    with jax.named_scope("embed"):
        x = params["embed"].astype(config.dtype)[tokens]
    return apply_stack(
        params["blocks"], x, config, pattern=pattern(config), attention_fn=attention_fn, mesh=mesh,
        num_microbatches=num_microbatches, seq_streams=_streams(tokens.shape[1], config),
    )[0]


def forward(
    params: Dict[str, Any],
    tokens,  # (B, S) int32
    config: GLM4MoELiteConfig,
    attention_fn: Optional[Callable] = None,
    dropout_rng=None,  # accepted for API parity; no dropout
    mesh=None,
    num_microbatches: Optional[int] = None,
    return_aux: bool = False,
    targets=None,  # (B, S): each position's next token, for the prediction module's loss
):
    """Logits (B, S, vocab) f32 against the head (untied); with `return_aux`,
    also the prediction module's weighted loss where the config has a module
    and `targets` are given (`stack.lm_loss` gives them), else None: the
    module reads position i's activations and `targets[i]` and predicts
    `targets[i + 1]`; each row's last position has no such target and is
    left out of the mean."""
    del dropout_rng
    x = hidden(params, tokens, config, attention_fn, mesh, num_microbatches)
    logits = lm_head(
        x, lambda x: rms_norm(x, params["final_norm"], config.norm_eps), params["lm_head"], config.dtype
    )
    if not return_aux:
        return logits
    aux = None
    if config.n_predict_layers and targets is not None:
        with jax.named_scope("mtp"):
            seq = targets.shape[1]
            after = jnp.roll(targets, -1, axis=1)  # a row's last wraps round, and is masked
            aux = config.mtp_loss_weight * causal_lm_loss(
                mtp_logits(params, x, targets, config, attention_fn, mesh), after,
                mask=jnp.arange(seq) < seq - 1)
    return logits, aux


# Mean next-token cross entropy plus the module's term: `stack.lm_loss`'s arguments after `forward`.
loss_fn = functools.partial(lm_loss, forward)


def routing_stats(params: Dict[str, Any], tokens, config: GLM4MoELiteConfig) -> Dict[str, Any]:
    """What the routers did with `tokens` (B, S + 1), a batch's rows as
    `loss_fn` takes them, per expert layer (leading axis, in the published
    order, the prediction module's layer last): `moe.routing_report`'s
    `experts` (L, B * S, k), `tokens_per_expert` (L, E), `load_max_over_mean`,
    `held_pairs`, `elsewhere_pairs`, `dropped` (counted, not assumed: 0) and
    `compact` (L,)."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x = params["embed"].astype(config.dtype)[inputs]
    streams = _streams(inputs.shape[1], config)
    pairs = inputs.size * config.experts_per_token
    walked = pattern(config, stats=True)
    per_layer = []

    def through(x, kind, layer):
        x, aux = block(x, layer, config, *walked.kinds[kind], streams=streams)
        if aux is not None:
            per_layer.append(routing_report(aux, pairs))
        return x

    for kind, layer in walked.layers(params["blocks"]):
        x = through(x, kind, layer)
    if config.n_predict_layers:
        through(mtp_input(params, x, targets, config), MOE, params["mtp"]["block"])
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *per_layer)
