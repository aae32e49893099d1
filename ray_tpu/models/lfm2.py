"""LFM2-MoE (LiquidAI, `model_type` `lfm2_moe`; the published sizes are
LFM2-24B-A2B's): a pre-norm stack whose layers differ in kind. Three in four
mix the positions with a gated short convolution and no attention; the others
with grouped-query attention whose q and k are RMS-normed per head. The first
`n_dense_layers` feed forward through one wide SwiGLU, every later layer
through a token-choice mixture of SwiGLU experts routed by sigmoid scores with
a selection bias.

    block:  h = x + Op(norm_op(x));  y = h + FFN(norm_ffn(h))
    conv:   (B, C, u) = split3(W_in n);  z = B * u;
            c_t = sum_{j<3} w[:, j] * z_{t-2+j}   (depthwise, causal, no bias)
            Op = W_out (C * c)
    attn:   32 query and 8 key/value heads of 64, q and k RMS-normed over the
            64, rotary on halves (theta 1e6), causal softmax, W_o
    experts: s = sigmoid(W_r n); the 4 largest of s + b; weights s at the
            chosen over their sum, times `routed_scaling_factor`

Built from what the zoo has: RMSNorm and the rotary tables are `llama.py`'s,
the patterned stack (leading layers, then a scan over periods; remat,
attention dispatch, head, loss) is `stack.py`'s, the expert layer is
`moe.moe_mlp`, which is told which experts this chip holds
(`n_experts_held`): the router scores all `n_experts`, and the layer returns
the part of the sum that its own experts give; what stands between the
convolution's two projections is `ops/short_conv.py gated_short_conv` (its
gradient one Mosaic kernel where the step is compiled for a TPU, PR 62). The
embedding is tied to the head. There is no auxiliary loss: the source balances by moving `expert_bias`
outside the loss; that rule is not in its `config.json`, and here the bias is
a seeded buffer that no optimizer step changes (`frozen_params`).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import apply_rope, rms_norm, rope_tables
from ray_tpu.models.moe import moe_mlp, routing_report, swiglu
from ray_tpu.models.stack import Pattern, apply_stack, block, draw, draw_layer, lm_head, lm_loss, lm_tree
from ray_tpu.ops.short_conv import gated_short_conv

CONV, ATTENTION = "conv", "full_attention"
PUBLISHED_LAYER_TYPES = (CONV, CONV) + (ATTENTION, CONV, CONV, CONV) * 9 + (ATTENTION, CONV)


@dataclasses.dataclass(frozen=True)
class LFM2Config:
    """Defaults are LFM2-24B-A2B's published sizes."""

    vocab_size: int = 65536
    layer_types: Tuple[str, ...] = PUBLISHED_LAYER_TYPES
    n_dense_layers: int = 2  # the first layers' feed-forward is one dense SwiGLU
    n_head: int = 32
    n_kv_head: int = 8
    d_model: int = 2048
    d_ff: int = 11776  # the dense SwiGLU (the source's `intermediate_size`)
    d_expert: int = 1536  # one expert (`moe_intermediate_size`)
    n_experts: int = 64  # the router's width
    experts_per_token: int = 4
    n_experts_held: Optional[int] = None  # experts computed here (None: all), ...
    first_expert_held: int = 0  # ... from this one on
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    conv_kernel: int = 3  # `conv_L_cache`
    max_seq_len: int = 128000
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    remat_policy: Optional[str] = "save_attn"  # as LlamaConfig's; a conv layer is recomputed whole
    attention: str = "auto"  # auto | flash | xla

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_head == 0
        return self.d_model // self.n_head

    @property
    def group_size(self) -> int:
        assert self.n_head % self.n_kv_head == 0
        return self.n_head // self.n_kv_head

    @property
    def held(self) -> int:
        return self.n_experts if self.n_experts_held is None else self.n_experts_held

    @classmethod
    def nano(cls, **kw):
        """Tiny config for CPU tests: two dense layers, two periods of four, 8
        experts of which this share holds 2, 2 a token."""
        kw.setdefault("vocab_size", 256)
        kw.setdefault("max_seq_len", 64)
        kw.setdefault("n_experts_held", 2)
        kw.setdefault("first_expert_held", 2)
        kw.setdefault("layer_types", (CONV, CONV) + (ATTENTION, CONV, CONV, CONV) * 2)
        return cls(n_dense_layers=2, n_head=4, n_kv_head=2, d_model=64, d_ff=160, d_expert=32,
                   n_experts=8, experts_per_token=2, **kw)


def layer_kinds(config: LFM2Config) -> Tuple[str, ...]:
    """Every layer's kind, `<operator>_<feed-forward>`: `conv_dense`, `full_attention_moe`, ..."""
    return tuple(f"{op}_{'dense' if i < config.n_dense_layers else 'moe'}"
                 for i, op in enumerate(config.layer_types))


def layout(config: LFM2Config) -> Tuple[Tuple[str, ...], Tuple[str, ...], int, Tuple[str, ...]]:
    """(leading, period, n_periods, trailing) kinds of `layer_types`: the dense
    layers lead; the others are a period repeated and what is left over of
    them trails, the period chosen so that the fewest layers are unrolled (a
    period's and the trailing ones), of two such the one that trails fewer."""
    kinds = layer_kinds(config)
    leading, rest = kinds[:config.n_dense_layers], kinds[config.n_dense_layers:]
    best = None
    for p in range(1, len(rest) + 1):
        n = 1
        while rest[n * p:(n + 1) * p] == rest[:p]:
            n += 1
        left = len(rest) - n * p
        if best is None or (p + left, left) < best[0]:
            best = ((p + left, left), (leading, rest[:p], n, rest[n * p:]))
    return best[1] if best else (leading, (), 0, ())


# --------------------------------------------------------------------------- sizes
def _kind_params(config: LFM2Config, kind: str) -> Dict[str, int]:
    """Parameters of one layer of `kind`: `matmul` that every token meets as an
    operand of a product, `experts` in all the experts held here, `other`."""
    d, kvd = config.d_model, config.n_kv_head * config.head_dim
    op, ffn = kind.rsplit("_", 1)
    matmul = 4 * d * d if op == CONV else 2 * d * d + 2 * d * kvd
    other = 2 * d + (config.conv_kernel * d if op == CONV else 2 * config.head_dim)
    experts = 0
    if ffn == "dense":
        matmul += 3 * d * config.d_ff
    else:
        matmul += d * config.n_experts
        other += config.n_experts  # expert_bias
        experts = 3 * config.held * d * config.d_expert
    return {"matmul": matmul, "experts": experts, "other": other}


def num_params(config: LFM2Config) -> int:
    """Of this share: the experts held, not all the router names."""
    return config.vocab_size * config.d_model + config.d_model + sum(
        sum(_kind_params(config, kind).values()) for kind in layer_kinds(config))


def train_flops_per_token(config: LFM2Config, seq_len: int) -> float:
    """6 FLOPs per matmul parameter a token meets here (of its
    `experts_per_token` experts the share `held / n_experts` that this chip
    computes, in expectation) plus full-square attention in the attention
    layers, as `gpt.py` counts."""
    kinds = layer_kinds(config)
    per_expert = 3 * config.d_model * config.d_expert
    pairs_here = config.experts_per_token * config.held / config.n_experts
    active = config.vocab_size * config.d_model + sum(
        _kind_params(config, kind)["matmul"]
        + (pairs_here * per_expert if kind.endswith("_moe") else 0) for kind in kinds)
    n_attention = sum(kind.startswith(ATTENTION) for kind in kinds)
    return 6.0 * active + 12.0 * n_attention * config.d_model * seq_len


# --------------------------------------------------------------------------- init
def _layer_shapes(config: LFM2Config, kind: str):
    """{name: (shape, init: a normal's std or "ones", logical axes)} of one layer of `kind`."""
    d, nh, nkv, hd = config.d_model, config.n_head, config.n_kv_head, config.head_dim
    std, out_std = 0.02, 0.02 / math.sqrt(2 * config.n_layer)
    op, ffn = kind.rsplit("_", 1)
    shapes: Dict[str, Any] = {"op_norm": ((d,), "ones", (None,)), "ffn_norm": ((d,), "ones", (None,))}
    if op == CONV:
        shapes.update({
            "conv_in": ((d, 3 * d), std, ("embed", "mlp")),
            # (taps, channels): tap j multiplies position t - (kernel - 1) + j.
            "conv_w": ((config.conv_kernel, d), config.conv_kernel ** -0.5, (None, None)),
            "conv_out": ((d, d), out_std, ("mlp", "embed")),
        })
    else:
        shapes.update({
            "wq": ((d, nh, hd), std, ("embed", "heads", None)),
            "wk": ((d, nkv, hd), std, ("embed", "kv_heads", None)),
            "wv": ((d, nkv, hd), std, ("embed", "kv_heads", None)),
            "q_norm": ((hd,), "ones", (None,)),
            "k_norm": ((hd,), "ones", (None,)),
            "wo": ((nh, hd, d), out_std, ("heads", None, "embed")),
        })
    if ffn == "dense":
        shapes.update({
            "w_gate": ((d, config.d_ff), std, ("embed", "mlp")),
            "w_up": ((d, config.d_ff), std, ("embed", "mlp")),
            "w_down": ((config.d_ff, d), out_std, ("mlp", "embed")),
        })
    else:
        held, f = config.held, config.d_expert
        shapes["moe"] = {
            "router_w": ((d, config.n_experts), std, ("embed", None)),
            "expert_bias": ((config.n_experts,), std, (None,)),
            "w_gate": ((held, d, f), std, ("expert", "embed", "mlp")),
            "w_up": ((held, d, f), std, ("expert", "embed", "mlp")),
            "w_down": ((held, f, d), out_std, ("expert", "mlp", "embed")),
        }
    return shapes


def _tree(config: LFM2Config, leaf: Callable, layers: Optional[Callable] = None):
    """`stack.lm_tree` of this model: a tree like the parameters', the head tied to the embedding."""
    return lm_tree(config, layout(config), functools.partial(_layer_shapes, config), leaf, layers)


def init_params(config: LFM2Config, key) -> Dict[str, Any]:
    pd = config.param_dtype
    k_embed, k_layers = jax.random.split(key)
    return _tree(
        config,
        lambda name, shape, init, axes: draw(k_embed, shape, init, pd),  # the embedding: the one leaf that reads it
        lambda kind, i, stack: draw_layer(jax.random.fold_in(k_layers, i), _layer_shapes(config, kind), stack, pd))


def param_logical_axes(config: LFM2Config) -> Dict[str, Any]:
    return _tree(config, lambda name, shape, init, axes: axes)


def frozen_params(config: LFM2Config) -> Dict[str, Any]:
    """True at the leaves that are buffers and no parameters (`expert_bias`):
    `make_train_step` applies no update to them, weight decay included."""
    return _tree(config, lambda name, shape, init, axes: name == "expert_bias")


# --------------------------------------------------------------------------- forward
def short_conv(h, layer, config: LFM2Config, mesh=None):
    """The gated short convolution on the normed h (B, S, D). `conv_mix` holds
    what is no matmul: the gate `B * u`, the taps and the gate `C * c`, in
    float32 inside one fusion, rounded once (`ops/short_conv.py
    gated_short_conv`; where the step is compiled for a TPU, chosen by the
    mesh's platform, its gradient is one Mosaic kernel that keeps `bcu` alone)."""
    cdt = config.dtype
    with jax.named_scope("short_conv"):
        bcu = jnp.einsum("bsd,de->bse", h, layer["conv_in"].astype(cdt))
        with jax.named_scope("conv_mix"):
            y = gated_short_conv(bcu, layer["conv_w"], mesh=mesh)
        return jnp.einsum("bsd,de->bse", y, layer["conv_out"].astype(cdt))


def _kinds(config: LFM2Config, stats: bool = False, mesh=None):
    """`stack.Pattern.kinds`: the parts of each kind of layer. x: (B, S, D);
    cos/sin: this rank's rows of the rotary tables. An `out_part` returns
    (x, aux): a zero, or with `stats` what `moe_mlp` reports of the layer
    (nothing for a dense one). The scope names are read from the compiled
    program's `op_name`s (PERF.md, "names"). `mesh`: `forward`'s, for the one
    part that may hold a Mosaic call and is handed no mesh by the stack."""
    cdt, eps = config.dtype, config.norm_eps

    def qkv_part(x, layer, cos, sin):
        h = rms_norm(x, layer["op_norm"], eps).astype(cdt)
        q = jnp.einsum("bsd,dnh->bnsh", h, layer["wq"].astype(cdt))
        k = jnp.einsum("bsd,dnh->bnsh", h, layer["wk"].astype(cdt))
        v = jnp.einsum("bsd,dnh->bnsh", h, layer["wv"].astype(cdt))
        # QK-norm per head: over the head's own 64, one scale of that width for all heads.
        q = apply_rope(rms_norm(q, layer["q_norm"], eps).astype(cdt), cos, sin)
        k = apply_rope(rms_norm(k, layer["k_norm"], eps).astype(cdt), cos, sin)
        if config.group_size > 1:
            k = jnp.repeat(k, config.group_size, axis=1)
            v = jnp.repeat(v, config.group_size, axis=1)
        return q, k, v

    def attention_out(x, o, layer):
        with jax.named_scope("attn_out"):
            return x + jnp.einsum("bnsh,nhd->bsd", o.astype(cdt), layer["wo"].astype(cdt))

    def conv_op(x, o, layer):
        del o  # no attention in this kind's middle
        with jax.named_scope("conv_norm"):  # the operator's norm: `short_conv` opens its scope after it
            h = rms_norm(x, layer["op_norm"], eps).astype(cdt)
        return x + short_conv(h, layer, config, mesh)

    def dense_ffn(x, layer):
        with jax.named_scope("dense_mlp"):
            h = rms_norm(x, layer["ffn_norm"], eps).astype(cdt)
            return x + swiglu(h, layer["w_gate"], layer["w_up"], layer["w_down"]), None

    def moe_ffn(x, layer):
        with jax.named_scope("moe"):
            h = rms_norm(x, layer["ffn_norm"], eps).astype(cdt)
            moe = layer["moe"]
            h, aux = moe_mlp(
                h, moe["router_w"], moe["w_gate"], moe["w_up"], moe["w_down"],
                k=config.experts_per_token, norm_topk_prob=config.norm_topk_prob,
                router_bias=moe["expert_bias"], weight_scale=config.routed_scaling_factor,
                held_from=config.first_expert_held)
            return x + h, aux

    def out_part(op, ffn):
        def part(x, o, layer, rng):
            del rng  # no dropout
            x, aux = ffn(op(x, o, layer), layer)
            return x, aux if stats else jnp.zeros((), jnp.float32)
        return part

    return {f"{name}_{ffn_name}": (qkv, out_part(op, ffn))
            for name, qkv, op in ((CONV, None, conv_op), (ATTENTION, qkv_part, attention_out))
            for ffn_name, ffn in (("dense", dense_ffn), ("moe", moe_ffn))}


def pattern(config: LFM2Config, stats: bool = False, mesh=None) -> Pattern:
    leading, period, n_periods, trailing = layout(config)
    return Pattern(_kinds(config, stats, mesh), period, n_periods, leading, trailing)


def forward(
    params: Dict[str, Any],
    tokens,  # (B, S) int32
    config: LFM2Config,
    attention_fn: Optional[Callable] = None,
    dropout_rng=None,  # accepted for API parity; no dropout
    mesh=None,
    num_microbatches: Optional[int] = None,
    return_aux: bool = False,
):
    """Logits (B, S, vocab) f32 against the tied embedding; with `return_aux`,
    also None: the model has no auxiliary loss."""
    del dropout_rng
    cdt = config.dtype
    with jax.named_scope("embed"):
        x = params["embed"].astype(cdt)[tokens]
    x, _ = apply_stack(
        params["blocks"], x, config, pattern=pattern(config, mesh=mesh), attention_fn=attention_fn, mesh=mesh,
        num_microbatches=num_microbatches,
        seq_streams=rope_tables(tokens.shape[1], config.head_dim, config.rope_theta),
    )
    logits = lm_head(
        x, lambda x: rms_norm(x, params["final_norm"], config.norm_eps), params["embed"], cdt
    )
    if return_aux:
        return logits, None
    return logits


# Mean next-token cross entropy: `stack.lm_loss`'s arguments after `forward`.
loss_fn = functools.partial(lm_loss, forward)


def routing_stats(params: Dict[str, Any], tokens, config: LFM2Config) -> Dict[str, Any]:
    """What the routers did with `tokens` (B, S), per expert layer (leading
    axis, in the published order): `moe.routing_report`'s `experts` (L, B * S,
    k), `tokens_per_expert` (L, E), `load_max_over_mean`, `held_pairs`,
    `elsewhere_pairs`, `dropped` (counted, not assumed: 0) and `compact` (L,)."""
    x = params["embed"].astype(config.dtype)[tokens]
    streams = rope_tables(tokens.shape[1], config.head_dim, config.rope_theta)
    pairs = tokens.size * config.experts_per_token
    walked = pattern(config, stats=True)
    per_layer = []
    for kind, layer in walked.layers(params["blocks"]):
        x, aux = block(x, layer, config, *walked.kinds[kind], streams=streams)
        if aux is not None:
            per_layer.append(routing_report(aux, pairs))
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *per_layer)
