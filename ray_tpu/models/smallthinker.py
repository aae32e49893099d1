"""SmallThinker (PowerInfer; the published sizes are SmallThinker-21BA3B-Instruct's,
21B-A3B, arXiv:2507.20984): a mixture of experts at every depth whose router
reads the layer's normed input, before attention, while the experts read the
post-attention normed state; ReGLU experts; grouped-query attention of 28
query heads on 4 key/value heads (groups of 7); layers in periods of four,
one over the whole past with no position at all and three over a sliding
window with a rotation.

    block:     n = N_in(x)
               router:    logits = n W_r in f32; the 6 largest; w = softmax over those six
                          (a softmax over all 64, the top 6, renormalised: the same numbers)
               attention: q = n W_q, k = n W_k, v = n W_v, no bias, no norm on q or k;
                          window layers: rotate-half rotary over all of a head on q and k,
                          softmax over the keys j of query i with 0 <= i - j < `sliding_window`
                          full layers:   no rotation, softmax over j <= i
               h = x + W_o o;  m = N_post(h)
               y = h + sum over the chosen e of w_e W_down,e (relu(W_gate,e m) * W_up,e m)
    stack:     every layer is such a layer (no dense layer, no shared expert, no selection
               bias, no auxiliary term); a final norm; an untied head

Built from what the zoo has: the projections and the leaves' shapes are
`gqa_experts.py`'s (less the head norms), RMSNorm and the rotary tables
`llama.py`'s, and the patterned stack, head and loss `stack.py`'s. The expert
layer is `moe.py`'s in its two halves: `route_and_sort` stands in the block's
first part, on n, so the router's gradient lands on the attention's input and
the sort does not wait for the attention; what it yields rides through the
kind's `attend` (`stack.block`: what a first part yields beside q, k, v) to the
second part, where `experts_of` reads m. The activation is a function this
model hands (`jax.nn.relu`). A window is `ops.flash_attention.SlidingWindow`, a
mask by structure.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import gqa_experts
from ray_tpu.models.llama import rms_norm, rope_tables
from ray_tpu.models.moe import experts_of, route_and_sort, routing_report
from ray_tpu.models.stack import Pattern, apply_stack, block, causal_lm_loss, draw, draw_layer, lm_head, lm_tree
from ray_tpu.models.trinity import kept_pairs  # the band's and the triangle's pairs: the same two masks

WINDOW, FULL = "window", "full"  # a layer's kind: windowed with a rotation, or the whole past with none


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    """Defaults are SmallThinker-21BA3B-Instruct's published sizes (the source's key where the name differs)."""

    vocab_size: int = 151936
    n_layer: int = 52  # num_hidden_layers
    sliding_window_layout: Tuple[int, ...] = (0, 1, 1, 1)  # 1: a window layer; ... repeated over `n_layer`
    rope_layout: Tuple[int, ...] = (0, 1, 1, 1)  # 1: the layer rotates q and k; the source's is the same list
    n_head: int = 28
    n_kv_head: int = 4
    head_dim: int = 128
    d_model: int = 2560
    d_expert: int = 768  # moe_ffn_hidden_size
    n_experts: int = 64  # the router's width (moe_num_primary_experts)
    experts_per_token: int = 6  # moe_num_active_primary_experts
    n_experts_held: Optional[int] = None  # routed experts computed here (None: all), ...
    first_expert_held: int = 0  # ... from this one on
    sliding_window: int = 4096  # sliding_window_size
    rope_theta: float = 1.5e6
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    remat_policy: Optional[str] = "save_attn"  # as LlamaConfig's

    def __post_init__(self):
        assert self.n_head % self.n_kv_head == 0
        for name in ("sliding_window_layout", "rope_layout"):
            layout = tuple(getattr(self, name))
            if len(layout) != self.n_layer:  # a period, repeated
                assert self.n_layer % len(layout) == 0, f"`{name}`: every layer's, or a period that divides them"
                layout = layout * (self.n_layer // len(layout))
            object.__setattr__(self, name, layout)
        assert self.rope_layout == self.sliding_window_layout, (
            "the two kinds written: a window layer that rotates, a full layer with no position")

    @property
    def held(self) -> int:
        return self.n_experts if self.n_experts_held is None else self.n_experts_held

    @property
    def kinds(self) -> Tuple[str, ...]:
        """Every layer's kind, in the published order."""
        return tuple(WINDOW if windowed else FULL for windowed in self.sliding_window_layout)

    @property
    def period(self) -> Tuple[str, ...]:
        """The shortest run of kinds that the stack repeats: (full, window, window, window) as published."""
        kinds = self.kinds
        return next(kinds[:p] for p in range(1, len(kinds) + 1)
                    if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p))

    @classmethod
    def nano(cls, **kw):
        """Tiny config for CPU tests: one period (a full layer, three window layers of 16 keys), 7 query heads on
        one key/value head of 16 (the group of 7 kept), 16 experts of which this share holds 4, 2 a token."""
        kw.setdefault("vocab_size", 256)
        kw.setdefault("n_layer", 4)
        kw.setdefault("n_experts_held", 4)
        kw.setdefault("first_expert_held", 4)
        kw.setdefault("sliding_window", 16)
        return cls(n_head=7, n_kv_head=1, head_dim=16, d_model=64, d_expert=32, n_experts=16, experts_per_token=2, **kw)


# --------------------------------------------------------------------------- sizes
def _layer_shapes(config: SmallThinkerConfig) -> Dict[str, Any]:
    """`gqa_experts.layer_shapes` (`attn_norm` is N_in, `mlp_norm` N_post) less the head norms: the source has none."""
    shapes = dict(gqa_experts.layer_shapes(config))
    del shapes["q_norm"], shapes["k_norm"]
    return shapes


def num_params(config: SmallThinkerConfig) -> int:
    """Of this share: the experts held, not all the router names; embedding and head untied."""
    layer = gqa_experts.layer_params(config) - 2 * config.head_dim  # no head norms
    return 2 * config.vocab_size * config.d_model + config.d_model + config.n_layer * layer


def train_flops_per_token(config: SmallThinkerConfig, seq_len: int) -> float:
    """6 FLOPs per matmul parameter a token meets here (of its `experts_per_token` experts the share `held /
    n_experts` that this chip computes, in expectation; the embedding is a lookup), and attention's two products
    forward and four backward on the pairs each layer's mask keeps: the band in a window layer, the triangle in a
    full one."""
    pairs_here = config.experts_per_token * config.held / config.n_experts
    active = config.vocab_size * config.d_model + config.n_layer * (
        gqa_experts.matmul_params(config) + pairs_here * 3 * config.d_model * config.d_expert)
    kept = sum(kept_pairs(seq_len, config.sliding_window if kind == WINDOW else None) for kind in config.kinds)
    return 6.0 * active + 12.0 * config.n_head * config.head_dim * kept / seq_len


# --------------------------------------------------------------------------- init
def _tree(config: SmallThinkerConfig, leaf: Callable, layers: Optional[Callable] = None):
    """`stack.lm_tree` of this model: no leading layer, the period's places stacked over the periods. The
    embedding's rows are N(0, 1), as `gqa_experts.tree` draws them for the family's other models and for their
    reason (at 0.02 the routers behind a group of query heads on one key/value head see one input)."""
    period = config.period
    layout = ((), period, config.n_layer // len(period), ())
    return lm_tree(config, layout, lambda kind: _layer_shapes(config), leaf, layers, embed=1.0, head="lm_head")


def init_params(config: SmallThinkerConfig, key) -> Dict[str, Any]:
    """Normal 0.02, the output projections (W_o, every down projection) 0.02 / sqrt(2 x layers), norm scales 1,
    the embedding's rows N(0, 1)."""
    pd = config.param_dtype
    k_leaves, k_layers = jax.random.split(key)
    names = ("embed", "final_norm", "lm_head")
    return _tree(
        config,
        lambda name, shape, init, axes: draw(jax.random.fold_in(k_leaves, names.index(name)), shape, init, pd),
        lambda kind, i, stack: draw_layer(jax.random.fold_in(k_layers, i), _layer_shapes(config), stack, pd))


def param_logical_axes(config: SmallThinkerConfig) -> Dict[str, Any]:
    return _tree(config, lambda name, shape, init, axes: axes)


# --------------------------------------------------------------------------- forward
def _relu_live(tokens, experts, w_gate, config: SmallThinkerConfig):
    """The hidden units of the held experts that ReLU leaves non-zero, counted over the (token, expert) pairs
    held here: for each held expert the gates `W_gate,e m` (bf16 operands, f32 sums: the grouped product's own)
    of the tokens that chose it, `> 0`. For `routing_stats` alone: the step counts nothing."""
    def of_expert(xs):
        e, w = xs
        chose = (experts == config.first_expert_held + e).any(axis=-1)
        gate = jnp.einsum("td,df->tf", tokens, w.astype(tokens.dtype), preferred_element_type=jnp.float32)
        return jnp.sum((gate > 0) & chose[:, None], dtype=jnp.int32)

    return jax.lax.map(of_expert, (jnp.arange(config.held), w_gate)).sum()


def _kinds(config: SmallThinkerConfig, stats: bool = False) -> Dict[str, tuple]:
    """`stack.Pattern.kinds`: (qkv_part, out_part, attend) of each kind. x: (B, S, D); cos/sin: this rank's rows of
    the rotary tables. A `qkv_part` yields q, k, v and what `route_and_sort` made of the same normed input (the
    routing and the router's report); the kind's `attend` hands that on beside o; an `out_part` returns (x, aux):
    nothing, or with `stats` what the two halves of the expert layer report and `relu_live`. Every part opens its
    kind's scope, so a trace tells the window layers' work from the full layer's; the scope names are read from the
    compiled program's `op_name`s (PERF.md, "names")."""
    from ray_tpu.ops.flash_attention import SlidingWindow, flash_attention

    cdt, eps, k = config.dtype, config.norm_eps, config.experts_per_token

    def qkv_part(kind):
        def part(x, layer, cos, sin):
            with jax.named_scope(kind):
                n = rms_norm(x, layer["attn_norm"], eps).astype(cdt)
                with jax.named_scope("moe"):
                    routed = route_and_sort(
                        n.reshape(-1, n.shape[-1]), layer["moe"]["router_w"], config.held, k=k,
                        norm_topk_prob=True, held_from=config.first_expert_held)  # a softmax over the chosen
                if kind == FULL:  # a full layer takes no position at all
                    cos = sin = None
                return (*gqa_experts.qkv_heads(n, layer, cos, sin, config), routed)
        return part

    def attend(kind):
        mask = SlidingWindow(config.sliding_window) if kind == WINDOW else True

        def call(q, k, v, routed, attention_fn, mesh):
            with jax.named_scope(kind):
                if attention_fn is not None:
                    if mask is not True:
                        raise NotImplementedError("a sliding window under an injected attention (ring, Ulysses)")
                    return attention_fn(q, k, v), routed
                if mesh is not None and int(mesh.shape.get("pipeline", 1)) > 1:
                    mesh = None  # as `stack.resolve_attention`: the pipeline's manual region cannot be reopened
                return flash_attention(q, k, v, causal=mask, mesh=mesh), routed
        return call

    def out_part(kind):
        def part(x, o, layer, rng, routed):
            del rng  # no dropout
            routing, aux = routed
            with jax.named_scope(kind):
                with jax.named_scope("attn_out"):
                    h = x + jnp.einsum("bnsh,nhd->bsd", o.astype(cdt), layer["wo"].astype(cdt))
                with jax.named_scope("moe"):
                    moe = layer["moe"]
                    m = rms_norm(h, layer["mlp_norm"], eps).astype(cdt).reshape(-1, h.shape[-1])
                    y, report = experts_of(m, routing, moe["w_gate"], moe["w_up"], moe["w_down"], k=k,
                                           n_experts=config.n_experts, act=jax.nn.relu)
                    if stats:
                        aux = {**aux, **report, "relu_live": _relu_live(m, aux["experts"], moe["w_gate"], config)}
                return h + y.reshape(h.shape), aux if stats else None
        return part

    return {kind: (qkv_part(kind), out_part(kind), attend(kind)) for kind in sorted(set(config.kinds))}


def pattern(config: SmallThinkerConfig, stats: bool = False) -> Pattern:
    return Pattern(_kinds(config, stats), config.period, config.n_layer // len(config.period))


def _embed(params, tokens, config: SmallThinkerConfig):
    with jax.named_scope("embed"):
        return params["embed"].astype(config.dtype)[tokens]


def _streams(seq_len: int, config: SmallThinkerConfig):
    return rope_tables(seq_len, config.head_dim, config.rope_theta)


def forward(
    params: Dict[str, Any],
    tokens,  # (B, S) int32
    config: SmallThinkerConfig,
    attention_fn: Optional[Callable] = None,
    dropout_rng=None,  # accepted for API parity; no dropout
    mesh=None,
    num_microbatches: Optional[int] = None,
    return_aux: bool = False,
):
    """Logits (B, S, vocab) f32 against the untied head; with `return_aux`, also None: the model has no
    auxiliary loss."""
    del dropout_rng
    x, _ = apply_stack(
        params["blocks"], _embed(params, tokens, config), config, pattern=pattern(config), attention_fn=attention_fn,
        mesh=mesh, num_microbatches=num_microbatches, seq_streams=_streams(tokens.shape[1], config), aux_per_layer=True)
    logits = lm_head(x, lambda x: rms_norm(x, params["final_norm"], config.norm_eps), params["lm_head"], config.dtype)
    return (logits, None) if return_aux else logits


def loss_fn(params, batch, config: SmallThinkerConfig, attention_fn=None, step_rng=None, mesh=None,
            num_microbatches=None):
    """Mean next-token cross entropy of `batch`, {"tokens": (B, S + 1)} or {"inputs", "targets"}."""
    del step_rng
    if "inputs" in batch:
        inputs, targets = batch["inputs"], batch["targets"]
    else:
        inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    return causal_lm_loss(forward(params, inputs, config, attention_fn, None, mesh, num_microbatches), targets)


def routing_stats(params: Dict[str, Any], tokens, config: SmallThinkerConfig) -> Dict[str, Any]:
    """What the routers did with `tokens` (B, S), per layer (leading axis, in the published order):
    `moe.routing_report`'s `experts` (L, B * S, k), `tokens_per_expert` (L, E), `load_max_over_mean`,
    `held_pairs`, `elsewhere_pairs`, `dropped` (counted, not assumed: 0), `compact` (L,); and `relu_live_share`
    (L,): of the held pairs' `d_expert` hidden units each, the share ReLU leaves non-zero (`_relu_live`)."""
    x = _embed(params, tokens, config)
    streams = _streams(tokens.shape[1], config)
    pairs = tokens.size * config.experts_per_token
    walked = pattern(config, stats=True)
    per_layer = []
    for kind, layer in walked.layers(params["blocks"]):
        qkv, out, own = walked.kinds[kind]
        x, aux = block(x, layer, config, qkv, out, streams=streams, attend=own)
        hidden = jnp.maximum(aux["held_pairs"], 1) * config.d_expert
        per_layer.append({**routing_report(aux, pairs), "relu_live_share": aux["relu_live"] / hidden})
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *per_layer)
