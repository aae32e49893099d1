"""Solar Open 2 (Upstage, `model_type` `solar_open2`; the published sizes are
Solar-Open2-250B's): a pre-norm mixture of experts in periods of one softmax
attention layer and three linear-attention layers. The linear layers are Kimi
Delta Attention (Kimi Linear, arXiv:2510.26692): a matrix-valued recurrent
state a head, written by a delta rule whose decay is a vector of `d_k` a head
and position; the attention layers are grouped-query attention with no
rotation and a sigmoid gate on their output. Every layer feeds forward through
a shared expert and a token-choice mixture of routed ones.

    block:   h = x + mixer(N(x));  y = h + shared(N(h)) + routed(N(h))
    kda:     q = l2norm(silu(conv4(W_q n))) d_k^-1/2,  k = l2norm(silu(conv4(W_k n)))
             v = silu(conv4(W_v n))                    `ops/short_conv.py`, all of it
             g = -exp(A_log) softplus(W_f_up (W_f_down n) + dt_bias)     (d_k a head, f32)
             beta = 2 sigmoid(w_b . n)                 (the 2: `allow_neg_eigval`)
             o = kimi_delta_rule(q, k, v, g, beta)     `ops/kda.py`
             mixer = W_o (sigmoid(W_g_up (W_g_down n) + b_g) * N_dv(o))
    gqa:     H query heads on H / group key/value heads of `head_dim`, no rotation,
             causal softmax at head_dim^-1/2 (the stack's attention dispatch)
             mixer = W_o (sigmoid(W_gate n) * o)       element-wise, a gate a channel
    experts: s = sigmoid(W_r n); the k largest; weights s at the chosen over their
             sum, times `routed_scaling_factor`; SwiGLU experts, dropless

A configuration may hold a share of a layer: `n_head` / `n_kv_head` /
`linear_heads` are the heads built here (every projection onto heads holds
those heads' columns, the two low-rank down-projections are whole), and
`n_experts_held` the routed experts (`moe.moe_mlp(held_from=)`: the router
scores all `n_experts`). What the absent heads and experts would add to a
token is left out, and the partial sum goes on.

Built from what the zoo has: RMSNorm is `llama.py`'s, the expert layer and the
shared expert `moe.py`'s, the patterned stack, head and loss `stack.py`'s, the
convolutions `ops/short_conv.py`. The linear kind brings its own `attend` (the
scan) to `stack.Pattern`; under "save_attn" the scan's residuals (q, k, v, the
gates and the chunks' states) are saved as the flash call's are. There is no
auxiliary loss: the family balances by a selection bias moved outside the
loss (DeepSeek-V3's, whose key names the source's `config.json` uses), the
source carries no key for one, and `moe.route`'s load-balance term is written
for scores that sum to one, which sigmoids do not.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import rms_norm
from ray_tpu.models.moe import moe_mlp, routing_report, shared_expert
from ray_tpu.models.stack import Pattern, apply_stack, block, draw, draw_layer, lm_head, lm_loss, lm_tree
from ray_tpu.ops import kda
from ray_tpu.ops.short_conv import short_conv

GQA, KDA = "gqa", "kda"
PERIOD = (GQA, KDA, KDA, KDA)  # `gqa_layers` 0, 4, 8, ...


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config:
    """Defaults are Solar-Open2-250B's published sizes."""

    vocab_size: int = 196608
    layer_types: Tuple[str, ...] = PERIOD * 12
    d_model: int = 4096
    n_head: int = 64  # the attention layers' query heads built here
    n_kv_head: int = 8
    head_dim: int = 128
    linear_heads: int = 64  # key heads = value heads, built here
    linear_key_dim: int = 128
    linear_value_dim: int = 128
    conv_kernel: int = 4
    gate_rank: int = 128  # the two low-rank gate projections' inner width
    allow_neg_eigval: bool = True  # beta in (0, 2)
    d_ff: int = 10240  # the source's `intermediate_size`: no layer is dense (`first_k_dense_replace` 0)
    d_expert: int = 1280
    n_experts: int = 320  # the router's width
    experts_per_token: int = 8
    n_experts_held: Optional[int] = None  # routed experts computed here (None: all), ...
    first_expert_held: int = 0  # ... from this one on
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    max_seq_len: int = 1048576
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    remat_policy: Optional[str] = "save_attn"
    attention: str = "auto"  # auto | flash | xla, the attention layers'

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def held(self) -> int:
        return self.n_experts if self.n_experts_held is None else self.n_experts_held

    @property
    def period(self) -> Tuple[str, ...]:
        """The shortest run of kinds that `layer_types` repeats."""
        types = self.layer_types
        return next(types[:p] for p in range(1, len(types) + 1)
                    if len(types) % p == 0 and types == types[:p] * (len(types) // p))

    @classmethod
    def nano(cls, **kw):
        """Tiny config for CPU tests: two periods, widths no multiple of a lane row, 16 experts of which this
        share holds 4, 2 a token."""
        kw.setdefault("vocab_size", 256)
        kw.setdefault("max_seq_len", 64)
        kw.setdefault("layer_types", PERIOD * 2)
        kw.setdefault("n_experts_held", 4)
        kw.setdefault("first_expert_held", 4)
        return cls(d_model=64, n_head=4, n_kv_head=2, head_dim=16, linear_heads=4, linear_key_dim=12,
                   linear_value_dim=24, gate_rank=8, d_ff=160, d_expert=32, n_experts=16, experts_per_token=2, **kw)


# --------------------------------------------------------------------------- sizes
def _kind_params(config: SolarOpen2Config, kind: str) -> Dict[str, int]:
    """Parameters of one layer of `kind` here: `matmul` that every token meets as an operand of a product,
    `experts` in all the routed experts held, `other`."""
    d, rank = config.d_model, config.gate_rank
    matmul = d * config.n_experts + 3 * d * config.d_expert  # the router, the shared expert
    other = 2 * d
    if kind == KDA:
        h = config.linear_heads
        keys, values = h * config.linear_key_dim, h * config.linear_value_dim
        matmul += d * (2 * keys + 2 * values) + 2 * d * rank + rank * (keys + values)
        other += d * h + config.conv_kernel * (2 * keys + values) + h + keys + values + config.linear_value_dim
    else:
        q, kv = config.n_head * config.head_dim, config.n_kv_head * config.head_dim
        matmul += 3 * d * q + 2 * d * kv
    return {"matmul": matmul, "experts": 3 * config.held * d * config.d_expert, "other": other}


def num_params(config: SolarOpen2Config) -> int:
    """Of this share: the heads built and the experts held, not all the source names."""
    return 2 * config.vocab_size * config.d_model + config.d_model + sum(
        sum(_kind_params(config, kind).values()) for kind in config.layer_types)


def train_flops_per_token(config: SolarOpen2Config, seq_len: int) -> float:
    """6 FLOPs per matmul parameter a token meets here (of its `experts_per_token` experts the share `held /
    n_experts` that this chip computes, in expectation; the embedding is a lookup) plus full-square attention
    over the query heads built here in the attention layers, as `gpt.py` counts; the scan's own products
    (`benchmark/models/solar_open2.py` counts them) are left out."""
    pairs_here = config.experts_per_token * config.held / config.n_experts
    active = config.vocab_size * config.d_model + sum(
        _kind_params(config, kind)["matmul"] + pairs_here * 3 * config.d_model * config.d_expert
        for kind in config.layer_types)
    return 6.0 * active + 12.0 * config.layer_types.count(GQA) * config.n_head * config.head_dim * seq_len


# --------------------------------------------------------------------------- init
def _layer_shapes(config: SolarOpen2Config, kind: str):
    """{name: (shape, how it starts, logical axes)} of one layer of `kind`. A start is a normal's std, "ones" for
    a norm's scale, "zeros" for a bias, or the name of a gate's own draw (`stack.draw`)."""
    d, taps, rank, f = config.d_model, config.conv_kernel, config.gate_rank, config.d_expert
    std, out_std = 0.02, 0.02 / math.sqrt(2 * config.n_layer)
    shapes: Dict[str, Any] = {
        "mixer_norm": ((d,), "ones", (None,)), "moe_norm": ((d,), "ones", (None,)),
        "moe": {
            "router_w": ((d, config.n_experts), std, ("embed", None)),
            "w_gate": ((config.held, d, f), std, ("expert", "embed", "mlp")),
            "w_up": ((config.held, d, f), std, ("expert", "embed", "mlp")),
            "w_down": ((config.held, f, d), out_std, ("expert", "mlp", "embed")),
            "shared_gate": ((d, f), std, ("embed", "mlp")),
            "shared_up": ((d, f), std, ("embed", "mlp")),
            "shared_down": ((f, d), out_std, ("mlp", "embed")),
        },
    }
    if kind == KDA:
        h = config.linear_heads
        keys, values = h * config.linear_key_dim, h * config.linear_value_dim
        shapes.update({
            "wq": ((d, keys), std, ("embed", "heads")), "wk": ((d, keys), std, ("embed", "heads")),
            "wv": ((d, values), std, ("embed", "heads")), "wo": ((values, d), out_std, ("heads", "embed")),
            # (taps, channels): tap j multiplies position t - (taps - 1) + j.
            "conv_q": ((taps, keys), taps ** -0.5, (None, None)),
            "conv_k": ((taps, keys), taps ** -0.5, (None, None)),
            "conv_v": ((taps, values), taps ** -0.5, (None, None)),
            "w_f_down": ((d, rank), std, ("embed", None)), "w_f_up": ((rank, keys), std, (None, "heads")),
            "A_log": ((h,), "A_log", (None,)), "dt_bias": ((keys,), "dt_bias", (None,)),
            "w_b": ((d, h), std, ("embed", None)),
            "w_g_down": ((d, rank), std, ("embed", None)), "w_g_up": ((rank, values), std, (None, "heads")),
            "b_g": ((values,), "zeros", (None,)),
            "o_norm": ((config.linear_value_dim,), "ones", (None,)),
        })
    else:
        nh, nkv, hd = config.n_head, config.n_kv_head, config.head_dim
        shapes.update({
            "wq": ((d, nh, hd), std, ("embed", "heads", None)),
            "wk": ((d, nkv, hd), std, ("embed", "kv_heads", None)),
            "wv": ((d, nkv, hd), std, ("embed", "kv_heads", None)),
            "w_gate": ((d, nh, hd), std, ("embed", "heads", None)),
            "wo": ((nh, hd, d), out_std, ("heads", None, "embed")),
        })
    return shapes


def _tree(config: SolarOpen2Config, leaf: Callable, layers: Optional[Callable] = None):
    """`stack.lm_tree` of this model: a tree like the parameters', a place of the period a stack over the periods.
    The embedding's rows are N(0, 1), `torch.nn.Embedding`'s own, as `gqa_experts.tree` draws them and for
    its reason: the first layer's group of query heads on one key/value head adds the running mean of the
    values up coherently, and at 0.02 the routers behind it would see one input for every token."""
    layout = ((), config.period, config.n_layer // len(config.period), ())
    return lm_tree(config, layout, functools.partial(_layer_shapes, config), leaf, layers, head="head", embed=1.0)


def init_params(config: SolarOpen2Config, key) -> Dict[str, Any]:
    pd = config.param_dtype
    keys = dict(zip(("embed", "head", "layers"), jax.random.split(key, 3)))
    return _tree(
        config,
        lambda name, shape, init, axes: draw(keys.get(name), shape, init, pd),
        lambda kind, place, stack: draw_layer(
            jax.random.fold_in(keys["layers"], place), _layer_shapes(config, kind), stack, pd))


def param_logical_axes(config: SolarOpen2Config) -> Dict[str, Any]:
    return _tree(config, lambda name, shape, init, axes: axes)


# --------------------------------------------------------------------------- forward
def _low_rank(n, down, up, cdt):
    """`(n W_down) W_up` in float32: operands in the compute dtype, f32 accumulation, the inner width kept f32."""
    inner = jnp.einsum("bsd,dr->bsr", n, down.astype(cdt), preferred_element_type=jnp.float32)
    return jnp.einsum("bsr,re->bse", inner.astype(cdt), up.astype(cdt), preferred_element_type=jnp.float32)


def kda_qkv(x, layer, config: SolarOpen2Config, mesh=None):
    """What the scan reads, of the layer's input x (B, S, D): q, k (B, H, S, d_k) and v (B, H, S, d_v) in the
    compute dtype, g (B, H, S, d_k) and beta (B, H, S) f32. `mesh`: what the step shards over, for the Mosaic
    call in `short_conv`'s gradient."""
    cdt, h, dk = config.dtype, config.linear_heads, config.linear_key_dim
    with jax.named_scope("kda"):
        n = rms_norm(x, layer["mixer_norm"], config.norm_eps).astype(cdt)
        q, k, v = (jnp.einsum("bsd,de->bse", n, layer[w].astype(cdt)) for w in ("wq", "wk", "wv"))
        with jax.named_scope("kda_conv"):
            conv = functools.partial(short_conv, heads=h, mesh=mesh)
            q = conv(q, layer["conv_q"], normalize=True, scale=dk ** -0.5)
            k = conv(k, layer["conv_k"], normalize=True)
            v = conv(v, layer["conv_v"])
        with jax.named_scope("kda_gates"):
            b, s, _ = n.shape
            f = _low_rank(n, layer["w_f_down"], layer["w_f_up"], cdt) + layer["dt_bias"].astype(jnp.float32)
            rate = jnp.exp(layer["A_log"].astype(jnp.float32))[None, :, None, None]
            g = -rate * jax.nn.softplus(f).reshape(b, s, h, dk).transpose(0, 2, 1, 3)
            to_heads = jnp.einsum("bsd,dh->bsh", n, layer["w_b"].astype(cdt), preferred_element_type=jnp.float32)
            beta = jax.nn.sigmoid(to_heads.transpose(0, 2, 1)) * (2.0 if config.allow_neg_eigval else 1.0)
        return q, k, v, g, beta


def kda_out(x, o, layer, config: SolarOpen2Config):
    """The mixer's output from the scan's o (B, H, S, d_v): the head norm, the low-rank sigmoid gate, W_o."""
    cdt = config.dtype
    with jax.named_scope("kda"), jax.named_scope("kda_out"):
        b, h, s, dv = o.shape
        n = rms_norm(x, layer["mixer_norm"], config.norm_eps).astype(cdt)
        gate = jax.nn.sigmoid(_low_rank(n, layer["w_g_down"], layer["w_g_up"], cdt) + layer["b_g"].astype(jnp.float32))
        o = rms_norm(o.transpose(0, 2, 1, 3), layer["o_norm"], config.norm_eps)  # (B, S, H, d_v) f32
        gated = (o.reshape(b, s, h * dv) * gate).astype(cdt)
        return jnp.einsum("bse,ed->bsd", gated, layer["wo"].astype(cdt))


def gqa_qkv(x, layer, config: SolarOpen2Config):
    """q (B, H, S, hd), k and v (B, H / group, S, hd) of the attention layer's input x: no rotation, no norm."""
    cdt = config.dtype
    n = rms_norm(x, layer["mixer_norm"], config.norm_eps).astype(cdt)
    return tuple(jnp.einsum("bsd,dnh->bnsh", n, layer[w].astype(cdt)) for w in ("wq", "wk", "wv"))


def gqa_out(x, o, layer, config: SolarOpen2Config):
    """The mixer's output from the attention's o (B, H, S, hd): the sigmoid gate a channel, then W_o."""
    cdt = config.dtype
    with jax.named_scope("attn_gate"):
        n = rms_norm(x, layer["mixer_norm"], config.norm_eps).astype(cdt)
        gate = jax.nn.sigmoid(jnp.einsum("bsd,dnh->bnsh", n, layer["w_gate"].astype(cdt),
                                         preferred_element_type=jnp.float32))
        o = (o.astype(jnp.float32) * gate).astype(cdt)
    with jax.named_scope("attn_out"):
        return jnp.einsum("bnsh,nhd->bsd", o, layer["wo"].astype(cdt))


def feed_forward(x, layer, config: SolarOpen2Config):
    """(what the routed experts held here add to x (B, S, D), what the shared expert adds, `moe_mlp`'s report).
    Across the shares of a layer the first are partial sums that add up; the second is the same on every
    share and counts once."""
    cdt = config.dtype
    with jax.named_scope("moe"):
        n = rms_norm(x, layer["moe_norm"], config.norm_eps).astype(cdt)
        moe = layer["moe"]
        routed, aux = moe_mlp(
            n, moe["router_w"], moe["w_gate"], moe["w_up"], moe["w_down"],
            k=config.experts_per_token, norm_topk_prob=config.norm_topk_prob,
            # Sigmoid scores with no selection bias: `route`'s other scoring, the bias a constant zero.
            router_bias=jnp.zeros((config.n_experts,), jnp.float32),
            weight_scale=config.routed_scaling_factor, held_from=config.first_expert_held)
        return routed, shared_expert(n, moe["shared_gate"], moe["shared_up"], moe["shared_down"]), aux


def _kinds(config: SolarOpen2Config, mesh=None, stats: bool = False):
    """`stack.Pattern.kinds`: (qkv_part, out_part) of the attention kind, (qkv_part, out_part, attend) of the
    linear one. An `out_part` returns (x, aux): a zero, or with `stats` what `moe_mlp` reports of the layer.
    The scope names are read from the compiled program's `op_name`s (PERF.md, "names"). `mesh`: `forward`'s,
    for the one part that holds a Mosaic call and is handed no mesh by the stack."""

    def out_part(mixer_out):
        def part(x, o, layer, rng):
            """h = x + mixer; y = h + shared(N(h)) + routed(N(h))."""
            del rng  # no dropout
            x = x + mixer_out(x, o, layer, config)
            routed, shared, aux = feed_forward(x, layer, config)
            return x + routed + shared, aux if stats else jnp.zeros((), jnp.float32)
        return part

    def scan(q, k, v, g, beta, attention_fn, mesh):
        del attention_fn  # the attention layers'
        if mesh is not None and int(mesh.shape.get("pipeline", 1)) > 1:
            mesh = None  # as `resolve_attention`: no second shard_map inside the pipeline's region
        with jax.named_scope("kda"):
            return (kda.kimi_delta_rule(q, k, v, g, beta, mesh=mesh),)

    if mesh is not None and int(mesh.shape.get("pipeline", 1)) > 1:
        mesh = None  # as `scan`
    return {KDA: (lambda x, layer: kda_qkv(x, layer, config, mesh), out_part(kda_out), scan),
            GQA: (lambda x, layer: gqa_qkv(x, layer, config), out_part(gqa_out))}


def pattern(config: SolarOpen2Config, mesh=None, stats: bool = False) -> Pattern:
    period = config.period
    return Pattern(_kinds(config, mesh, stats), period, config.n_layer // len(period))


def forward(
    params: Dict[str, Any],
    tokens,  # (B, S) int32
    config: SolarOpen2Config,
    attention_fn: Optional[Callable] = None,
    dropout_rng=None,  # accepted for API parity; no dropout
    mesh=None,
    num_microbatches: Optional[int] = None,
    return_aux: bool = False,
):
    """Logits (B, S, vocab) f32 against the untied head; with `return_aux`, also None: the model has no
    auxiliary loss."""
    del dropout_rng
    cdt = config.dtype
    with jax.named_scope("embed"):
        x = params["embed"].astype(cdt)[tokens]
    x, _ = apply_stack(params["blocks"], x, config, pattern=pattern(config, mesh), attention_fn=attention_fn,
                       mesh=mesh, num_microbatches=num_microbatches)
    logits = lm_head(x, lambda x: rms_norm(x, params["final_norm"], config.norm_eps), params["head"], cdt)
    return (logits, None) if return_aux else logits


# Mean next-token cross entropy: `stack.lm_loss`'s arguments after `forward`.
loss_fn = functools.partial(lm_loss, forward)


def routing_stats(params: Dict[str, Any], tokens, config: SolarOpen2Config) -> Dict[str, Any]:
    """What the routers did with `tokens` (B, S), per layer (leading axis, in the published order):
    `moe.routing_report`'s `experts` (L, B * S, k), `tokens_per_expert` (L, E), `load_max_over_mean`,
    `held_pairs`, `elsewhere_pairs`, `dropped` (counted, not assumed: 0) and `compact` (L,)."""
    x = params["embed"].astype(config.dtype)[tokens]
    pairs = tokens.size * config.experts_per_token
    walked = pattern(config, stats=True)
    per_layer = []
    for kind, layer in walked.layers(params["blocks"]):
        qkv, out, *own = walked.kinds[kind]
        x, aux = block(x, layer, config, qkv, out, attend=own[0] if own else None)
        per_layer.append(routing_report(aux, pairs))
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *per_layer)
