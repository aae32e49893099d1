"""Olmo-Hybrid (AllenAI, `model_type` `olmo_hybrid`; the published sizes are
Olmo-Hybrid-7B's): a dense stack in periods of three linear-attention layers
and one full-attention layer. The linear layers are Gated DeltaNet layers
(Yang et al. 2024): a matrix-valued recurrent state a head, written by a gated
delta rule; the full layers are OLMo 2's attention with the QK-norm over the
whole projection. Both kinds norm after the sub-layer, not before it.

    block:   h = x + N(mixer(x));  y = h + N(mlp(h));  mlp = W_d (silu(W_g h) * W_u h)
    linear:  q = silu(conv4(W_q x)), k = silu(conv4(W_k x))   H heads of d_k
             v = silu(conv4(W_v x))                           H heads of d_v
             conv4: causal, depthwise, `conv_kernel` taps, no bias
             q, k L2-normalised over a head, q scaled by d_k^-1/2      `ops/short_conv.py`, all of it
             beta = 2 sigmoid(w_b . x)          (the 2: `allow_neg_eigval`)
             g = -exp(A_log) softplus(w_a . x + dt_bias)       f32
             o = gated_delta_rule(q, k, v, g, beta)            `ops/gated_delta_rule.py`
             mixer = W_o (N_dv(o) * silu(W_z x))   the norm over a head's d_v, one scale of d_v
    full:    q = N_D(W_q x), k = N_D(W_k x) over the whole projection, H heads
             of D / H, causal softmax attention at head_dim^-1/2, W_o; no rotary
             (the source's `rope_theta` is null: position reaches these layers
             through the recurrent ones)

Built from what the zoo has: RMSNorm is `llama.py`'s, the SwiGLU `moe.py`'s,
the patterned stack, head and loss `stack.py`'s. The linear kind brings its own `attend` (the scan) to
`stack.Pattern`, the full kind keeps the attention dispatch: under
"save_attn" the scan's residuals (q, k, v, the gates and the chunks' states)
are saved as the flash call's are, and neither kernel runs again in the
backward pass. Every matrix is stored as a matrix, its `embed` axis first or
last, and shards over it (FSDP) as GPT-2's do.

From a projection's bf16 output to the scan's bf16 operand q, k and v go through
`ops/short_conv.py short_conv` (PR 54): the convolution, SiLU, the move to
heads-first, the L2 norm and q's scale in float32, rounded once at the end, which
are the roundings this file made before it. Forward it is the same chain of
XLA operations on every platform. Its gradient is one Mosaic kernel
(`short_conv_bwd`, under the scope `gdn_conv`) where the step is compiled for a
TPU, chosen by the mesh's platform as the scan's kernels are; it reads z and
the cotangent and keeps nothing else of the forward pass, so the remat of the
layer's first part does not run the chain a second time. Off the TPU (and at the
nano size, whose widths fill no lane row) jax differentiates the chain as before.
`linear_out`'s gated norm was measured beside it and left as it is (PERF.md
section 6, PR 54).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import rms_norm
from ray_tpu.models.moe import swiglu
from ray_tpu.models.stack import Pattern, apply_stack, draw, draw_layer, lm_head, lm_loss, lm_tree
from ray_tpu.ops import gated_delta_rule as gdn
from ray_tpu.ops.short_conv import short_conv

LINEAR, FULL = "linear_attention", "full_attention"
PERIOD = (LINEAR, LINEAR, LINEAR, FULL)


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    """Defaults are Olmo-Hybrid-7B's published sizes."""

    vocab_size: int = 100352
    layer_types: Tuple[str, ...] = PERIOD * 8
    n_head: int = 30  # the full layers': heads of d_model / n_head
    d_model: int = 3840
    d_ff: int = 11008
    linear_heads: int = 30  # key heads = value heads
    linear_key_dim: int = 96
    linear_value_dim: int = 192
    conv_kernel: int = 4
    allow_neg_eigval: bool = True  # beta in (0, 2)
    max_seq_len: int = 65536
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    remat_policy: Optional[str] = "save_attn"
    attention: str = "auto"  # auto | flash | xla, the full layers'

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_head == 0
        return self.d_model // self.n_head

    @property
    def period(self) -> Tuple[str, ...]:
        """The shortest run of kinds that `layer_types` repeats."""
        types = self.layer_types
        return next(types[:p] for p in range(1, len(types) + 1)
                    if len(types) % p == 0 and types == types[:p] * (len(types) // p))

    @classmethod
    def nano(cls, **kw):
        """Tiny config for CPU tests: two periods, widths no multiple of a lane row."""
        kw.setdefault("vocab_size", 256)
        kw.setdefault("max_seq_len", 64)
        kw.setdefault("layer_types", PERIOD * 2)
        return cls(n_head=4, d_model=64, d_ff=160, linear_heads=4, linear_key_dim=12, linear_value_dim=24, **kw)


# --------------------------------------------------------------------------- sizes
def _kind_params(config: OlmoHybridConfig, kind: str) -> Dict[str, int]:
    """Parameters of one layer of `kind`: `matmul` that a token meets as an operand of a product, `other`."""
    d, h = config.d_model, config.linear_heads
    keys, values = h * config.linear_key_dim, h * config.linear_value_dim
    matmul, other = 3 * d * config.d_ff, 2 * d
    if kind == LINEAR:
        matmul += d * (2 * keys + 3 * values)
        other += 2 * d * h + config.conv_kernel * (2 * keys + values) + 2 * h + config.linear_value_dim
    else:
        matmul += 4 * d * d
        other += 2 * d
    return {"matmul": matmul, "other": other}


def num_params(config: OlmoHybridConfig) -> int:
    return 2 * config.vocab_size * config.d_model + config.d_model + sum(
        sum(_kind_params(config, kind).values()) for kind in config.layer_types)


def train_flops_per_token(config: OlmoHybridConfig, seq_len: int) -> float:
    """6 FLOPs per matmul parameter a token meets (the embedding is a lookup)
    plus full-square attention in the full layers, as `gpt.py` counts; the
    scan's own products (`benchmark/models/olmo_hybrid.py` counts them) are left out."""
    active = config.vocab_size * config.d_model + sum(
        _kind_params(config, kind)["matmul"] for kind in config.layer_types)
    return 6.0 * active + 12.0 * config.layer_types.count(FULL) * config.d_model * seq_len


# --------------------------------------------------------------------------- init
def _layer_shapes(config: OlmoHybridConfig, kind: str):
    """{name: (shape, how it starts, logical axes)} of one layer of `kind`. A start is a
    normal's std, "ones" for a norm's scale, or the name of a gate's own draw (`stack.draw`)."""
    d, h, taps = config.d_model, config.linear_heads, config.conv_kernel
    keys, values = h * config.linear_key_dim, h * config.linear_value_dim
    std, out_std = 0.02, 0.02 / math.sqrt(2 * config.n_layer)
    shapes: Dict[str, Any] = {
        "mixer_norm": ((d,), "ones", (None,)), "mlp_norm": ((d,), "ones", (None,)),
        "w_gate": ((d, config.d_ff), std, ("embed", "mlp")),
        "w_up": ((d, config.d_ff), std, ("embed", "mlp")),
        "w_down": ((config.d_ff, d), out_std, ("mlp", "embed")),
    }
    if kind == LINEAR:
        shapes.update({
            "wq": ((d, keys), std, ("embed", "heads")), "wk": ((d, keys), std, ("embed", "heads")),
            "wv": ((d, values), std, ("embed", "heads")), "wz": ((d, values), std, ("embed", "heads")),
            "wo": ((values, d), out_std, ("heads", "embed")),
            # (taps, channels): tap j multiplies position t - (taps - 1) + j.
            "conv_q": ((taps, keys), taps ** -0.5, (None, None)),
            "conv_k": ((taps, keys), taps ** -0.5, (None, None)),
            "conv_v": ((taps, values), taps ** -0.5, (None, None)),
            "w_a": ((d, h), std, ("embed", None)), "w_b": ((d, h), std, ("embed", None)),
            "A_log": ((h,), "A_log", (None,)), "dt_bias": ((h,), "dt_bias", (None,)),
            "o_norm": ((config.linear_value_dim,), "ones", (None,)),
        })
    else:
        shapes.update({
            "wq": ((d, d), std, ("embed", "heads")), "wk": ((d, d), std, ("embed", "heads")),
            "wv": ((d, d), std, ("embed", "heads")), "wo": ((d, d), out_std, ("heads", "embed")),
            "q_norm": ((d,), "ones", (None,)), "k_norm": ((d,), "ones", (None,)),
        })
    return shapes


def _tree(config: OlmoHybridConfig, leaf: Callable, layers: Optional[Callable] = None):
    """`stack.lm_tree` of this model: a tree like the parameters', a place of the period a stack over the periods."""
    layout = ((), config.period, config.n_layer // len(config.period), ())
    return lm_tree(config, layout, functools.partial(_layer_shapes, config), leaf, layers, head="head")


def init_params(config: OlmoHybridConfig, key) -> Dict[str, Any]:
    pd = config.param_dtype
    keys = dict(zip(("embed", "head", "layers"), jax.random.split(key, 3)))
    return _tree(
        config,
        lambda name, shape, init, axes: draw(keys.get(name), shape, init, pd),
        lambda kind, place, stack: draw_layer(
            jax.random.fold_in(keys["layers"], place), _layer_shapes(config, kind), stack, pd))


def param_logical_axes(config: OlmoHybridConfig) -> Dict[str, Any]:
    return _tree(config, lambda name, shape, init, axes: axes)


# --------------------------------------------------------------------------- forward
def _heads_first(x, heads: int):
    """(B, S, heads * d) -> (B, heads, S, d)."""
    b, s, _ = x.shape
    return x.reshape(b, s, heads, -1).transpose(0, 2, 1, 3)


def linear_qkv(x, layer, config: OlmoHybridConfig, mesh=None):
    """What the scan reads, of the layer's input x (B, S, D): q, k (B, H, S,
    d_k) and v (B, H, S, d_v) in the compute dtype, g and beta (B, H, S) f32.
    `mesh`: what the step shards over, for the Mosaic call in `short_conv`'s gradient."""
    cdt = config.dtype
    x = x.astype(cdt)
    with jax.named_scope("gdn"):
        q, k, v = (jnp.einsum("bsd,de->bse", x, layer[w].astype(cdt)) for w in ("wq", "wk", "wv"))
        with jax.named_scope("gdn_conv"):
            conv = functools.partial(short_conv, heads=config.linear_heads, mesh=mesh)
            q = conv(q, layer["conv_q"], normalize=True, scale=config.linear_key_dim ** -0.5)
            k = conv(k, layer["conv_k"], normalize=True)
            v = conv(v, layer["conv_v"])
        with jax.named_scope("gdn_gates"):
            xf = x.astype(jnp.float32)
            gate = lambda w: jnp.einsum("bsd,dh->bhs", xf, layer[w].astype(jnp.float32),  # noqa: E731
                                        precision=jax.lax.Precision.HIGHEST)
            beta = jax.nn.sigmoid(gate("w_b")) * (2.0 if config.allow_neg_eigval else 1.0)
            a, dt_bias = (layer[w].astype(jnp.float32)[None, :, None] for w in ("A_log", "dt_bias"))
            g = -jnp.exp(a) * jax.nn.softplus(gate("w_a") + dt_bias)
        return q, k, v, g, beta


def linear_out(x, o, layer, config: OlmoHybridConfig):
    """The mixer's output from the scan's o (B, H, S, d_v): the gated norm a head, then W_o."""
    cdt = config.dtype
    with jax.named_scope("gdn"), jax.named_scope("gdn_out"):
        b, h, s, dv = o.shape
        z = jnp.einsum("bsd,de->bse", x.astype(cdt), layer["wz"].astype(cdt)).astype(jnp.float32)
        o = rms_norm(o.transpose(0, 2, 1, 3), layer["o_norm"], config.norm_eps)  # (B, S, H, d_v) f32
        gated = (o.reshape(b, s, h * dv) * jax.nn.silu(z)).astype(cdt)
        return jnp.einsum("bse,ed->bsd", gated, layer["wo"].astype(cdt))


def _kinds(config: OlmoHybridConfig, mesh=None):
    """`stack.Pattern.kinds`: (qkv_part, out_part) of the full kind, (qkv_part,
    out_part, attend) of the linear one. The scope names are read from the
    compiled program's `op_name`s (PERF.md, "names"). `mesh`: `forward`'s, for
    the one part that holds a Mosaic call and is handed no mesh by the stack."""
    cdt, eps = config.dtype, config.norm_eps

    def finish(x, mixed, layer):
        """h = x + N(mixer(x)); y = h + N(mlp(h))."""
        h = x + rms_norm(mixed, layer["mixer_norm"], eps).astype(cdt)
        with jax.named_scope("dense_mlp"):
            y = swiglu(h, layer["w_gate"], layer["w_up"], layer["w_down"])
            return h + rms_norm(y, layer["mlp_norm"], eps).astype(cdt), jnp.zeros((), jnp.float32)

    def full_qkv(x, layer):
        x = x.astype(cdt)
        q, k, v = (jnp.einsum("bsd,de->bse", x, layer[w].astype(cdt)) for w in ("wq", "wk", "wv"))
        # OLMo 2's QK-norm: over the whole projection, before it is cut into heads.
        q = rms_norm(q, layer["q_norm"], eps).astype(cdt)
        k = rms_norm(k, layer["k_norm"], eps).astype(cdt)
        return tuple(_heads_first(z, config.n_head) for z in (q, k, v))

    def full_out(x, o, layer, rng):
        del rng  # no dropout
        with jax.named_scope("attn_out"):
            b, h, s, hd = o.shape
            mixed = jnp.einsum("bse,ed->bsd", o.transpose(0, 2, 1, 3).reshape(b, s, h * hd).astype(cdt),
                               layer["wo"].astype(cdt))
        return finish(x, mixed, layer)

    def scan(q, k, v, g, beta, attention_fn, mesh):
        del attention_fn  # the full layers'
        if mesh is not None and int(mesh.shape.get("pipeline", 1)) > 1:
            mesh = None  # as `resolve_attention`: no second shard_map inside the pipeline's region
        with jax.named_scope("gdn"):
            return (gdn.gated_delta_rule(q, k, v, g, beta, mesh=mesh),)

    def linear_out_part(x, o, layer, rng):
        del rng
        return finish(x, linear_out(x, o, layer, config), layer)

    if mesh is not None and int(mesh.shape.get("pipeline", 1)) > 1:
        mesh = None  # as `scan`: no second shard_map inside the pipeline's region
    return {LINEAR: (lambda x, layer: linear_qkv(x, layer, config, mesh), linear_out_part, scan),
            FULL: (full_qkv, full_out)}


def pattern(config: OlmoHybridConfig, mesh=None) -> Pattern:
    period = config.period
    return Pattern(_kinds(config, mesh), period, config.n_layer // len(period))


def forward(
    params: Dict[str, Any],
    tokens,  # (B, S) int32
    config: OlmoHybridConfig,
    attention_fn: Optional[Callable] = None,
    dropout_rng=None,  # accepted for API parity; no dropout
    mesh=None,
    num_microbatches: Optional[int] = None,
    return_aux: bool = False,
):
    """Logits (B, S, vocab) f32 against the untied head; with `return_aux`,
    also None: the model has no auxiliary loss."""
    del dropout_rng
    cdt = config.dtype
    with jax.named_scope("embed"):
        x = params["embed"].astype(cdt)[tokens]
    x, _ = apply_stack(params["blocks"], x, config, pattern=pattern(config, mesh), attention_fn=attention_fn,
                       mesh=mesh, num_microbatches=num_microbatches)
    logits = lm_head(x, lambda x: rms_norm(x, params["final_norm"], config.norm_eps), params["head"], cdt)
    if return_aux:
        return logits, None
    return logits


# Mean next-token cross entropy: `stack.lm_loss`'s arguments after `forward`.
loss_fn = functools.partial(lm_loss, forward)
