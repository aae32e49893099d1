"""Trinity (Arcee, `model_type` `afmoe`; the published sizes are Trinity-Mini's,
26B-A3B): a mixture of experts whose attention layers are of two kinds in
periods of four, three over a sliding window with a rotation and one over the
whole past with none, every sublayer between two norms, and whose router's
selection bias the train step itself moves.

    block:     h = x + N_post_attn(attn(N_in(x)));  y = h + N_post_mlp(ff(N_pre_mlp(h)))
    attention: n = N_in(x); q = N_q(W_q n), k = N_k(W_k n) (an RMSNorm over each head's own
               dimensions), v = W_v n; 32 query heads on 4 key/value heads of 128;
               window layers: rotate-half rotary over all of a head on q and k, softmax over
               the keys j of query i with 0 <= i - j < `sliding_window`
               full layers:   no rotation, softmax over j <= i
               attn = W_o (sigmoid(W_g n) * o), the gate element-wise, a gate a channel
    ff:        the first `n_dense_layers` a SwiGLU of `d_ff`; the others
               shared(m) + sum over the chosen of w_e expert_e(m), all SwiGLU of `d_expert`:
               s = sigmoid(W_r m) in f32; the 8 largest of s + b; w = `route_scale` x s at the
               chosen over their sum; dropless
    buffer:    after each step, outside the gradient, a layer at a time (`update_buffers`):
               c_e the pairs the step's tokens sent to expert e, d = `load_balance_coeff` x
               sign(mean(c) - c), b <- b + d - mean(d)   (torchtitan's auxiliary-loss-free
               rule, whose key the source's `config.json` carries)
    embedding: the rows times sqrt(d_model) (the source's `mup_enabled`); a final norm; an untied head

Built from what the zoo has: the projections, the head norms and the leaves'
shapes are `gqa_experts.py`'s (shared with `keye_vl2.py` and `sdar.py`),
RMSNorm and the rotary tables `llama.py`'s, the routed experts `moe.moe_mlp`
told which experts this chip holds (`n_experts_held`), the shared expert
`moe.shared_expert`, the dense SwiGLU `moe.swiglu` under the `dense_mlp` scope,
and the patterned stack, head and loss `stack.py`'s: each softmax kind brings
its own `attend`, which hands its mask to `flash_attention` (a window is
`ops.flash_attention.SlidingWindow`, a mask by structure: the kernels walk the
band and nothing else). There is no auxiliary loss: `load_balance_coeff` is
the step of the bias rule and no loss weight, and `moe.route`'s load-balance
term is written for scores that sum to one, which sigmoids do not.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import gqa_experts
from ray_tpu.models.llama import rms_norm, rope_tables
from ray_tpu.models.moe import moe_mlp, routing_report, shared_expert, swiglu
from ray_tpu.models.stack import Pattern, apply_stack, block, causal_lm_loss, draw, draw_layer, lm_head, lm_tree

WINDOW, FULL = "window", "full"  # the softmax kinds; a dense layer's kind is `dense_<kind>`
SOURCE_KINDS = {"sliding_attention": WINDOW, "full_attention": FULL}  # the source's `layer_types`
DENSE = "dense_"


@dataclasses.dataclass(frozen=True)
class TrinityConfig:
    """Defaults are Trinity-Mini's published sizes (the source's key where the name differs)."""

    vocab_size: int = 200192
    layer_types: Tuple[str, ...] = ("sliding_attention",) * 3 + ("full_attention",)  # ... repeated over `n_layer`
    n_layer: int = 32  # num_hidden_layers
    n_dense_layers: int = 2  # num_dense_layers: the first layers' feed-forward is one dense SwiGLU
    n_head: int = 32
    n_kv_head: int = 4
    head_dim: int = 128
    d_model: int = 2048
    d_ff: int = 6144  # the dense SwiGLU (`intermediate_size`)
    d_expert: int = 1024  # one expert, and the shared one (`moe_intermediate_size`)
    n_experts: int = 128  # the router's width (`num_experts`)
    experts_per_token: int = 8
    n_experts_held: Optional[int] = None  # routed experts computed here (None: all), ...
    first_expert_held: int = 0  # ... from this one on
    route_scale: float = 2.826
    load_balance_coeff: float = 0.001  # the bias rule's step (torchtitan's key)
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    remat_policy: Optional[str] = "save_attn"  # as LlamaConfig's

    def __post_init__(self):
        assert self.n_head % self.n_kv_head == 0 and 0 <= self.n_dense_layers < self.n_layer
        assert set(self.layer_types) <= set(SOURCE_KINDS), self.layer_types
        if len(self.layer_types) != self.n_layer:  # a period, repeated
            assert self.n_layer % len(self.layer_types) == 0, "`layer_types`: every layer's, or a period that divides them"
            object.__setattr__(self, "layer_types", self.layer_types * (self.n_layer // len(self.layer_types)))

    @property
    def held(self) -> int:
        return self.n_experts if self.n_experts_held is None else self.n_experts_held

    @property
    def kinds(self) -> Tuple[str, ...]:
        """Every layer's kind, in the published order: `window` or `full`, `dense_` before it in the first layers."""
        return tuple((DENSE if i < self.n_dense_layers else "") + SOURCE_KINDS[t]
                     for i, t in enumerate(self.layer_types))

    @classmethod
    def nano(cls, **kw):
        """Tiny config for CPU tests: a dense window layer and one period (three window layers of 16 keys, one full
        layer), 16 experts of which this share holds 4, 2 a token; 4 query heads on 2 key/value heads of 16."""
        kw.setdefault("vocab_size", 256)
        kw.setdefault("layer_types", ("sliding_attention",) * 4 + ("full_attention",))
        kw.setdefault("n_layer", len(kw["layer_types"]))
        kw.setdefault("n_dense_layers", 1)
        kw.setdefault("n_experts_held", 4)
        kw.setdefault("first_expert_held", 4)
        kw.setdefault("sliding_window", 16)
        return cls(n_head=4, n_kv_head=2, head_dim=16, d_model=64, d_ff=160, d_expert=32, n_experts=16,
                   experts_per_token=2, **kw)


def split(config: TrinityConfig) -> Tuple[int, Tuple[str, ...]]:
    """(the leading layers' count, one period's kinds): the stack as `stack.Pattern` takes it. The leading layers
    hold every dense one; behind them the kinds repeat. Of the splits that do, the one with the fewest layer bodies
    to compile (leading + period): Trinity-Mini's 32 are 4 leading (two dense) and 7 periods of (window, window,
    window, full); a cut of five layers with one dense is 1 leading and one such period."""
    kinds, best = config.kinds, None
    for n_lead in range(config.n_dense_layers, config.n_layer):
        rest = kinds[n_lead:]
        p = next(p for p in range(1, len(rest) + 1) if len(rest) % p == 0 and rest == rest[:p] * (len(rest) // p))
        if best is None or n_lead + p < best[0] + len(best[1]):
            best = (n_lead, rest[:p])
    return best


# --------------------------------------------------------------------------- sizes
def kept_pairs(seq_len: int, window: Optional[int]) -> int:
    """(query, key) pairs of one head that a layer's mask keeps on a row of `seq_len`: the triangle, or with
    `window` the band (query i keeps min(i + 1, window) keys)."""
    w = seq_len if window is None else min(window, seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w


def _kind_params(config: TrinityConfig, kind: str) -> Dict[str, int]:
    """Parameters of one layer of `kind` here: `matmul` that every token meets as an operand of a product,
    `experts` in the routed experts held, `other` (four norms, two head norms, the selection bias)."""
    d, q, kv = config.d_model, config.n_head * config.head_dim, config.n_kv_head * config.head_dim
    matmul = 3 * d * q + 2 * d * kv  # W_q, the gate, W_o; W_k, W_v
    other, experts = 4 * d + 2 * config.head_dim, 0
    if kind.startswith(DENSE):
        matmul += 3 * d * config.d_ff
    else:
        matmul += d * config.n_experts + 3 * d * config.d_expert  # the router, the shared expert
        other += config.n_experts
        experts = 3 * config.held * d * config.d_expert
    return {"matmul": matmul, "experts": experts, "other": other}


def num_params(config: TrinityConfig) -> int:
    """Of this share: the experts held, not all the router names; embedding and head untied."""
    return 2 * config.vocab_size * config.d_model + config.d_model + sum(
        sum(_kind_params(config, kind).values()) for kind in config.kinds)


def train_flops_per_token(config: TrinityConfig, seq_len: int) -> float:
    """6 FLOPs per matmul parameter a token meets here (of its `experts_per_token` experts the share `held /
    n_experts` that this chip computes, in expectation; the embedding is a lookup), and attention's two products
    forward and four backward on the pairs each layer's mask keeps: the band in a window layer, the triangle in a
    full one."""
    pairs_here = config.experts_per_token * config.held / config.n_experts
    active = config.vocab_size * config.d_model + sum(
        _kind_params(config, kind)["matmul"]
        + (0 if kind.startswith(DENSE) else pairs_here * 3 * config.d_model * config.d_expert)
        for kind in config.kinds)
    kept = sum(kept_pairs(seq_len, config.sliding_window if kind.endswith(WINDOW) else None) for kind in config.kinds)
    return 6.0 * active + 12.0 * config.n_head * config.head_dim * kept / seq_len


# --------------------------------------------------------------------------- init
def _layer_shapes(config: TrinityConfig, kind: str) -> Dict[str, Any]:
    """{name: (shape, init, logical axes)} of one layer of `kind`: `gqa_experts.layer_shapes` (`attn_norm` is N_in,
    `mlp_norm` N_pre_mlp) with the two norms after the sublayers, the output gate, and for an expert layer the
    selection bias (zeros, torchtitan's start) and the shared expert; a dense layer's SwiGLU in the expert layer's
    place."""
    d, nh, hd, f = config.d_model, config.n_head, config.head_dim, config.d_expert
    std, out_std = 0.02, 0.02 / math.sqrt(2 * config.n_layer)
    shapes = dict(gqa_experts.layer_shapes(config))
    shapes.update({"post_attn_norm": ((d,), "ones", (None,)), "post_mlp_norm": ((d,), "ones", (None,)),
                   "wg": ((d, nh, hd), std, ("embed", "heads", None))})
    if kind.startswith(DENSE):
        del shapes["moe"]
        shapes.update({"w_gate": ((d, config.d_ff), std, ("embed", "mlp")),
                       "w_up": ((d, config.d_ff), std, ("embed", "mlp")),
                       "w_down": ((config.d_ff, d), out_std, ("mlp", "embed"))})
    else:
        shapes["moe"] = {**shapes["moe"],
                         "expert_bias": ((config.n_experts,), "zeros", (None,)),
                         "shared_gate": ((d, f), std, ("embed", "mlp")),
                         "shared_up": ((d, f), std, ("embed", "mlp")),
                         "shared_down": ((f, d), out_std, ("mlp", "embed"))}
    return shapes


def _tree(config: TrinityConfig, leaf: Callable, layers: Optional[Callable] = None):
    """`stack.lm_tree` of this model: a tree like the parameters'.
    The embedding starts at 0.02: times sqrt(d_model) its rows enter the stack at an RMS of 0.9, the unit rows
    `gqa_experts.tree` draws for the family's other models, and for their reason."""
    n_lead, period = split(config)
    layout = (config.kinds[:n_lead], period, (config.n_layer - n_lead) // len(period), ())
    return lm_tree(config, layout, functools.partial(_layer_shapes, config), leaf, layers, head="lm_head")


def init_params(config: TrinityConfig, key) -> Dict[str, Any]:
    """Normal 0.02, the output projections (W_o, every down projection) 0.02 / sqrt(2 x layers), norm scales 1,
    `expert_bias` 0."""
    pd = config.param_dtype
    k_leaves, k_layers = jax.random.split(key)
    names = ("embed", "final_norm", "lm_head")
    return _tree(
        config,
        lambda name, shape, init, axes: draw(jax.random.fold_in(k_leaves, names.index(name)), shape, init, pd),
        lambda kind, i, stack: draw_layer(jax.random.fold_in(k_layers, i), _layer_shapes(config, kind), stack, pd))


def param_logical_axes(config: TrinityConfig) -> Dict[str, Any]:
    return _tree(config, lambda name, shape, init, axes: axes)


def frozen_params(config: TrinityConfig) -> Dict[str, Any]:
    """True at `expert_bias`: a buffer that no optimizer step changes, weight decay included (`make_train_step`);
    `update_buffers` is what moves it."""
    return _tree(config, lambda name, shape, init, axes: name == "expert_bias")


# --------------------------------------------------------------------------- the buffer's rule
def bias_update(bias, counts, coeff: float):
    """The auxiliary-loss-free rule on one layer (or a stack of them: the last axis is the experts'): with `counts`
    the (token, expert) pairs the step sent to each expert, `d = coeff x sign(mean(counts) - counts)`, `bias + d -
    mean(d)`. An expert at the mean moves by `-mean(d)` alone; the update sums to zero over the experts."""
    counts = counts.astype(jnp.float32)
    d = coeff * jnp.sign(counts.mean(axis=-1, keepdims=True) - counts)
    return (bias.astype(jnp.float32) + d - d.mean(axis=-1, keepdims=True)).astype(bias.dtype)


def update_buffers(params: Dict[str, Any], stats, config: TrinityConfig) -> Dict[str, Any]:
    """`params` with every expert layer's `expert_bias` moved by `bias_update` on the step's own counts: `stats`
    is what `loss_fn` hands out beside the loss, the layers' `tokens_per_expert` laid out as `params["blocks"]`
    is (nothing for a dense layer). Under a mesh the counts are sums over every row of the batch, whichever
    chip holds it: the deployment's data-parallel sum. `make_train_step` calls it after the optimizer."""
    def layer(tree, counts):
        if counts is None:
            return tree
        moe = tree["moe"]
        return {**tree, "moe": {**moe, "expert_bias": bias_update(moe["expert_bias"], counts, config.load_balance_coeff)}}

    blocks = params["blocks"]
    moved = {place: [layer(tree, counts) for tree, counts in zip(blocks[place], stats[place])]
             for place in ("leading", "period", "trailing")}
    return {**params, "blocks": moved}


# --------------------------------------------------------------------------- forward
def _attention_out(x, o, layer, config: TrinityConfig):
    """h = x + N_post_attn(W_o (sigmoid(W_g N_in(x)) * o)) from the attention's o (B, H, S, hd)."""
    cdt, eps = config.dtype, config.norm_eps
    with jax.named_scope("attn_gate"):
        n = rms_norm(x, layer["attn_norm"], eps).astype(cdt)
        gate = jax.nn.sigmoid(jnp.einsum("bsd,dnh->bnsh", n, layer["wg"].astype(cdt),
                                         preferred_element_type=jnp.float32))
        o = (o.astype(jnp.float32) * gate).astype(cdt)
    with jax.named_scope("attn_out"):
        a = jnp.einsum("bnsh,nhd->bsd", o, layer["wo"].astype(cdt))
    with jax.named_scope("post_norm"):
        return x + rms_norm(a, layer["post_attn_norm"], eps).astype(cdt)


def feed_forward(m, moe, config: TrinityConfig):
    """(what the routed experts held here add for m (B, S, D), the expert layer's normed input; what the shared
    expert adds; `moe_mlp`'s report). Across the shares of a layer the first are partial sums that add up; the
    second is the same on every share and counts once."""
    routed, aux = moe_mlp(
        m, moe["router_w"], moe["w_gate"], moe["w_up"], moe["w_down"],
        k=config.experts_per_token, norm_topk_prob=True, router_bias=moe["expert_bias"],
        weight_scale=config.route_scale, held_from=config.first_expert_held)
    return routed, shared_expert(m, moe["shared_gate"], moe["shared_up"], moe["shared_down"]), aux


def _kinds(config: TrinityConfig, stats: bool = False) -> Dict[str, tuple]:
    """`stack.Pattern.kinds`: (qkv_part, out_part, attend) of each kind. x: (B, S, D); cos/sin: this rank's rows of
    the rotary tables. An `out_part` returns (x, aux): an expert layer's `tokens_per_expert` (nothing for a dense
    layer), or with `stats` all that `moe_mlp` reports. Every part opens its kind's scope, so a trace tells the
    window layers' work from the full layer's; the scope names are read from the compiled program's `op_name`s
    (PERF.md, "names")."""
    from ray_tpu.ops.flash_attention import SlidingWindow, flash_attention

    cdt, eps = config.dtype, config.norm_eps

    def qkv_part(kind):
        def part(x, layer, cos, sin):
            with jax.named_scope(kind):
                h = rms_norm(x, layer["attn_norm"], eps).astype(cdt)
                if not kind.endswith(WINDOW):  # a full layer takes no rotation
                    cos = sin = None
                return gqa_experts.qkv_heads(h, layer, cos, sin, config)
        return part

    def attend(kind):
        mask = SlidingWindow(config.sliding_window) if kind.endswith(WINDOW) else True

        def call(q, k, v, attention_fn, mesh):
            with jax.named_scope(kind):
                if attention_fn is not None:
                    if mask is not True:
                        raise NotImplementedError("a sliding window under an injected attention (ring, Ulysses)")
                    return (attention_fn(q, k, v),)
                if mesh is not None and int(mesh.shape.get("pipeline", 1)) > 1:
                    mesh = None  # as `stack.resolve_attention`: the pipeline's manual region cannot be reopened
                return (flash_attention(q, k, v, causal=mask, mesh=mesh),)
        return call

    def dense_ffn(m, layer):
        with jax.named_scope("dense_mlp"):
            return swiglu(m, layer["w_gate"], layer["w_up"], layer["w_down"]), None

    def moe_ffn(m, layer):
        with jax.named_scope("moe"):
            routed, shared, aux = feed_forward(m, layer["moe"], config)
            return routed + shared, aux if stats else aux["tokens_per_expert"]

    def out_part(kind):
        ffn = dense_ffn if kind.startswith(DENSE) else moe_ffn

        def part(x, o, layer, rng):
            del rng  # no dropout
            with jax.named_scope(kind):
                h = _attention_out(x, o, layer, config)
                y, aux = ffn(rms_norm(h, layer["mlp_norm"], eps).astype(cdt), layer)
                with jax.named_scope("post_norm"):
                    return h + rms_norm(y, layer["post_mlp_norm"], eps).astype(cdt), aux
        return part

    return {kind: (qkv_part(kind), out_part(kind), attend(kind)) for kind in sorted(set(config.kinds))}


def pattern(config: TrinityConfig, stats: bool = False) -> Pattern:
    n_lead, period = split(config)
    return Pattern(_kinds(config, stats), period, (config.n_layer - n_lead) // len(period), config.kinds[:n_lead])


def _embed(params, tokens, config: TrinityConfig):
    with jax.named_scope("embed"):
        return params["embed"].astype(config.dtype)[tokens] * jnp.asarray(math.sqrt(config.d_model), config.dtype)


def _streams(seq_len: int, config: TrinityConfig):
    return rope_tables(seq_len, config.head_dim, config.rope_theta)


def forward(
    params: Dict[str, Any],
    tokens,  # (B, S) int32
    config: TrinityConfig,
    attention_fn: Optional[Callable] = None,
    dropout_rng=None,  # accepted for API parity; no dropout
    mesh=None,
    num_microbatches: Optional[int] = None,
    return_aux: bool = False,
):
    """Logits (B, S, vocab) f32 against the untied head; with `return_aux`, also the expert layers'
    `tokens_per_expert`, laid out as `params["blocks"]` is (nothing for a dense layer): what `update_buffers`
    reads. The model has no auxiliary loss."""
    del dropout_rng
    x, counts = apply_stack(
        params["blocks"], _embed(params, tokens, config), config, pattern=pattern(config), attention_fn=attention_fn,
        mesh=mesh, num_microbatches=num_microbatches, seq_streams=_streams(tokens.shape[1], config), aux_per_layer=True)
    logits = lm_head(x, lambda x: rms_norm(x, params["final_norm"], config.norm_eps), params["lm_head"], config.dtype)
    return (logits, counts) if return_aux else logits


def loss_fn(params, batch, config: TrinityConfig, attention_fn=None, step_rng=None, mesh=None, num_microbatches=None):
    """(mean next-token cross entropy of `batch`, {"tokens": (B, S + 1)} or {"inputs", "targets"}; the step's
    statistics: `forward`'s counts). A model that defines `update_buffers` returns the pair, and
    `make_train_step` keeps the second out of the gradient pass."""
    del step_rng
    if "inputs" in batch:
        inputs, targets = batch["inputs"], batch["targets"]
    else:
        inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    logits, counts = forward(params, inputs, config, attention_fn, None, mesh, num_microbatches, return_aux=True)
    return causal_lm_loss(logits, targets), jax.lax.stop_gradient(counts)


def routing_stats(params: Dict[str, Any], tokens, config: TrinityConfig) -> Dict[str, Any]:
    """What the routers did with `tokens` (B, S), per expert layer (leading axis, in the published order):
    `moe.routing_report`'s `experts` (L, B * S, k), `tokens_per_expert` (L, E), `load_max_over_mean`,
    `held_pairs`, `elsewhere_pairs`, `dropped` (counted, not assumed: 0), `compact` (L,); and `bias_abs_max`
    (L,), the largest |b| of the layer's selection bias: how far the rule has moved it."""
    x = _embed(params, tokens, config)
    streams = _streams(tokens.shape[1], config)
    pairs = tokens.size * config.experts_per_token
    walked = pattern(config, stats=True)
    per_layer = []
    for kind, layer in walked.layers(params["blocks"]):
        qkv, out, own = walked.kinds[kind]
        x, aux = block(x, layer, config, qkv, out, streams=streams, attend=own)
        if aux is not None:
            per_layer.append({**routing_report(aux, pairs),
                              "bias_abs_max": jnp.abs(layer["moe"]["expert_bias"]).max()})
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *per_layer)
