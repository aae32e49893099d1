"""Granite 4.0-H (IBM, `model_type` `granitemoehybrid`; the published sizes are granite-4.0-h-micro's, the dense
member with no experts): a pre-norm stack in periods of ten layers, nine Mamba-2 mixers (state-space duality, Dao & Gu
2024) round one grouped-query attention layer without positions, a SwiGLU in every layer, and four muP multipliers.

    block:     h = x + r mixer(N(x));  y = h + r mlp(N(h));  mlp(n) = W_d (silu(W_g n) * W_u n);  r = 0.22
    mamba:     [z | xBC | dt] = n W_in        (stored as its three column blocks `w_z`, `w_xbc`, `w_dt`)
               xBC = silu(conv4(xBC) + bias)  causal, depthwise, over x, B and C together      `ops/short_conv.py`
               x: H heads of P;  B, C: `mamba_groups` groups of N, a group for its H / groups consecutive heads
               dt = softplus(dt + dt_bias)    a head and position, f32
               S_t = exp(dt_t A) S_{t-1} + B_t (dt_t x_t)^T,  o_t = S_t^T C_t + D x_t;  A = -exp(A_log)   `ops/ssd.py`
               mixer = W_out N_g(o * silu(z)) the gate before the norm; the RMS over all H P channels, a scale of that width
    attention: q, k, v of `n_head` query heads on `n_kv_head` key/value heads, no rotation (`position_embedding_type`
               "nope"), causal softmax of the scores times `attention_multiplier` (1/64 at heads of 64: not 64^-1/2)
    ends:      x_0 = `embedding_multiplier` E[token];  logits = (N(x_L) E^T) / `logits_scaling`, E tied

Built from what the zoo has: RMSNorm is `llama.py`'s, the SwiGLU `moe.py`'s, the attention layer's projections
`gqa_experts.qkv_heads`' path without tables, the patterned stack, head and loss `stack.py`'s. Both kinds bring their own
`attend` to `stack.Pattern`: the mamba kind the scan, the attention kind the dispatch with its own softmax scale
(`resolve_attention(sm_scale=)`: the scale is handed to the kernel, not folded into q, so q stays what the reference's
is). Under "save_attn" the scan's residuals (x, B, C, dt, v = dt x and the chunks' states) are saved as the flash
call's are, and neither kernel runs again in the backward pass.

To the convolution B's and C's 2 N channels are four more heads of P = 64 behind x's 64: `short_conv(heads=68)`
hands all of them back heads first, x is the first H and B, C the rest, a group's two halves side by side again
(4 MB a layer-row). Off the TPU, and where the widths fill no lane row, jax differentiates the chain.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.gqa_experts import qkv_heads
from ray_tpu.models.llama import rms_norm
from ray_tpu.models.moe import swiglu
from ray_tpu.models.stack import Pattern, apply_stack, draw, draw_layer, lm_head, lm_loss, lm_tree, resolve_attention
from ray_tpu.ops import ssd
from ray_tpu.ops.short_conv import short_conv

MAMBA, ATTENTION = "mamba", "attention"
PERIOD = (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4  # attention at 5, 15, 25, 35


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """Defaults are granite-4.0-h-micro's published sizes."""

    vocab_size: int = 100352
    layer_types: Tuple[str, ...] = PERIOD * 4
    d_model: int = 2048
    d_ff: int = 8192  # `shared_intermediate_size`: the SwiGLU of every layer
    n_head: int = 32
    n_kv_head: int = 8
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    mamba_state: int = 128
    mamba_groups: int = 1  # B and C are one array for `mamba_heads / mamba_groups` heads
    conv_kernel: int = 4
    ssd_chunk: int = ssd.CHUNK
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 1 / 64
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    max_seq_len: int = 131072
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
    def head_dim(self) -> int:
        assert self.d_model % self.n_head == 0
        return self.d_model // self.n_head

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        return self.mamba_inner + 2 * self.mamba_groups * self.mamba_state

    @property
    def period(self) -> Tuple[str, ...]:
        """The shortest run of kinds that `layer_types` repeats."""
        types = self.layer_types
        return next(types[:p] for p in range(1, len(types) + 1)
                    if len(types) % p == 0 and types == types[:p] * (len(types) // p))

    @classmethod
    def nano(cls, **kw):
        """Tiny config for CPU tests: two mamba kinds round one attention kind, four heads of 16 on one B and C of
        16, four query heads on two key/value heads, every multiplier other than 1."""
        kw.setdefault("vocab_size", 256)
        kw.setdefault("max_seq_len", 64)
        kw.setdefault("layer_types", (MAMBA, ATTENTION, MAMBA))
        kw.setdefault("ssd_chunk", 16)
        kw.setdefault("attention_multiplier", 1 / 16)
        return cls(d_model=32, d_ff=80, n_head=4, n_kv_head=2, mamba_heads=4, mamba_head_dim=16, mamba_state=16, **kw)


# --------------------------------------------------------------------------- sizes
def _kind_params(config: GraniteHybridConfig, kind: str) -> Dict[str, int]:
    """Parameters of one layer of `kind`: `matmul` that a token meets as an operand of a product, `other`."""
    d = config.d_model
    matmul, other = 3 * d * config.d_ff, 2 * d
    if kind == MAMBA:
        inner, channels, h = config.mamba_inner, config.conv_channels, config.mamba_heads
        matmul += d * (inner + channels + h) + inner * d
        other += (config.conv_kernel + 1) * channels + 3 * h + inner
    else:
        matmul += 2 * d * d + 2 * d * config.n_kv_head * config.head_dim
    return {"matmul": matmul, "other": other}


def num_params(config: GraniteHybridConfig) -> int:
    """The table counts once: the head is the embedding."""
    return config.vocab_size * config.d_model + config.d_model + sum(
        sum(_kind_params(config, kind).values()) for kind in config.layer_types)


def train_flops_per_token(config: GraniteHybridConfig, seq_len: int) -> float:
    """6 FLOPs per matmul parameter a token meets (the table once, as the head) plus the causal half of the square in
    the attention layers; the scan's own products (`benchmark/models/granite_hybrid.py` counts them) are left out."""
    active = config.vocab_size * config.d_model + sum(
        _kind_params(config, kind)["matmul"] for kind in config.layer_types)
    return 6.0 * active + 6.0 * config.layer_types.count(ATTENTION) * config.d_model * (seq_len + 1)


# --------------------------------------------------------------------------- init
def _layer_shapes(config: GraniteHybridConfig, kind: str):
    """{name: (shape, how it starts, logical axes)} of one layer of `kind`. A start is a normal's std, "ones" for a
    norm's scale and `D`, "zeros" for the convolution's bias, or the name of a gate's own draw (`stack.draw`)."""
    d, taps = config.d_model, config.conv_kernel
    std, out_std = 0.02, 0.02 / math.sqrt(2 * config.n_layer)
    shapes: Dict[str, Any] = {
        "mixer_norm": ((d,), "ones", (None,)), "mlp_norm": ((d,), "ones", (None,)),
        "w_gate": ((d, config.d_ff), std, ("embed", "mlp")),
        "w_up": ((d, config.d_ff), std, ("embed", "mlp")),
        "w_down": ((config.d_ff, d), out_std, ("mlp", "embed")),
    }
    if kind == MAMBA:
        inner, channels, h = config.mamba_inner, config.conv_channels, config.mamba_heads
        shapes.update({
            "w_z": ((d, inner), std, ("embed", "heads")), "w_xbc": ((d, channels), std, ("embed", None)),
            "w_dt": ((d, h), std, ("embed", None)), "w_out": ((inner, d), out_std, ("heads", "embed")),
            # (taps, channels): tap j multiplies position t - (taps - 1) + j.
            "conv_w": ((taps, channels), taps ** -0.5, (None, None)), "conv_b": ((channels,), "zeros", (None,)),
            "A_log": ((h,), "A_log", (None,)), "dt_bias": ((h,), "dt_bias", (None,)), "D": ((h,), "ones", (None,)),
            "gate_norm": ((inner,), "ones", (None,)),
        })
    else:
        nh, nkv, hd = config.n_head, config.n_kv_head, config.head_dim
        shapes.update({
            "wq": ((d, nh, hd), std, ("embed", "heads", None)),
            "wk": ((d, nkv, hd), std, ("embed", "kv_heads", None)),
            "wv": ((d, nkv, hd), std, ("embed", "kv_heads", None)),
            "wo": ((nh, hd, d), out_std, ("heads", None, "embed")),
        })
    return shapes


def _tree(config: GraniteHybridConfig, leaf: Callable, layers: Optional[Callable] = None):
    """`stack.lm_tree` of this model: a tree like the parameters', a place of the period a stack over the periods;
    no head of its own (the table is tied)."""
    layout = ((), config.period, config.n_layer // len(config.period), ())
    return lm_tree(config, layout, functools.partial(_layer_shapes, config), leaf, layers)


def init_params(config: GraniteHybridConfig, key) -> Dict[str, Any]:
    pd = config.param_dtype
    keys = dict(zip(("embed", "layers"), jax.random.split(key)))
    return _tree(
        config,
        lambda name, shape, init, axes: draw(keys.get(name), shape, init, pd),
        lambda kind, place, stack: draw_layer(
            jax.random.fold_in(keys["layers"], place), _layer_shapes(config, kind), stack, pd))


def param_logical_axes(config: GraniteHybridConfig) -> Dict[str, Any]:
    return _tree(config, lambda name, shape, init, axes: axes)


# --------------------------------------------------------------------------- forward
def mamba_inputs(x, layer, config: GraniteHybridConfig, mesh=None):
    """What the scan reads, of the layer's input x (B, S, D): (C, B (B, groups, S, N), x (B, H, S, P) in the compute
    dtype, dt (B, H, S) f32 after its softplus, `A_log`, `D`), in `stack.block`'s order of q, k, v and more.
    `mesh`: what the step shards over, for the Mosaic call in `short_conv`'s gradient."""
    cdt, h, p = config.dtype, config.mamba_heads, config.mamba_head_dim
    groups, state = config.mamba_groups, config.mamba_state
    with jax.named_scope("ssd"):
        n = rms_norm(x, layer["mixer_norm"], config.norm_eps).astype(cdt)
        xbc = jnp.einsum("bsd,de->bse", n, layer["w_xbc"].astype(cdt))
        with jax.named_scope("ssd_conv"):
            # B and C as further heads of P behind x's: a group's N channels are N / P of them, side by side again below
            assert state % p == 0, "a group's state is whole heads' widths to the convolution"
            conv = short_conv(xbc, layer["conv_w"], config.conv_channels // p, bias=layer["conv_b"], mesh=mesh)
            b, s = conv.shape[0], conv.shape[2]
            grouped = lambda z: z.reshape(b, groups, state // p, s, p).transpose(0, 1, 3, 2, 4).reshape(  # noqa: E731
                b, groups, s, state)
            heads = groups * state // p
            xs, bs, cs = conv[:, :h], grouped(conv[:, h:h + heads]), grouped(conv[:, h + heads:])
        with jax.named_scope("ssd_gates"):
            dt = jnp.einsum("bsd,dh->bsh", n, layer["w_dt"].astype(cdt), preferred_element_type=jnp.float32)
            dt = jax.nn.softplus(dt.transpose(0, 2, 1) + layer["dt_bias"].astype(jnp.float32)[None, :, None])
        return cs, bs, xs, dt, layer["A_log"], layer["D"]


def mamba_out(x, o, layer, config: GraniteHybridConfig):
    """The mixer's output from the scan's o (B, H, S, P): the gate, the norm over all channels (f32), W_out."""
    cdt = config.dtype
    with jax.named_scope("ssd"), jax.named_scope("ssd_norm"):
        b, h, s, p = o.shape
        n = rms_norm(x, layer["mixer_norm"], config.norm_eps).astype(cdt)
        z = jnp.einsum("bsd,de->bse", n, layer["w_z"].astype(cdt)).astype(jnp.float32)
        gated = o.transpose(0, 2, 1, 3).reshape(b, s, h * p).astype(jnp.float32) * jax.nn.silu(z)
        normed = rms_norm(gated, layer["gate_norm"], config.norm_eps).astype(cdt)
        return jnp.einsum("bse,ed->bsd", normed, layer["w_out"].astype(cdt))


def _kinds(config: GraniteHybridConfig, mesh=None):
    """`stack.Pattern.kinds`: (qkv_part, out_part, attend) of both kinds. The scope names are read from the compiled
    program's `op_name`s (PERF.md, "names"). `mesh`: `forward`'s, for the one part that holds a Mosaic call and is
    handed no mesh by the stack."""
    cdt, eps, r = config.dtype, config.norm_eps, config.residual_multiplier

    def finish(x, mixed, layer):
        """h = x + r mixer; y = h + r mlp(N(h))."""
        h = x + (r * mixed).astype(cdt)
        with jax.named_scope("dense_mlp"):
            n = rms_norm(h, layer["mlp_norm"], eps).astype(cdt)
            y = swiglu(n, layer["w_gate"], layer["w_up"], layer["w_down"])
            return h + (r * y).astype(cdt), jnp.zeros((), jnp.float32)

    def attention_qkv(x, layer):
        return qkv_heads(rms_norm(x, layer["mixer_norm"], eps).astype(cdt), layer, None, None, config)

    def attend(q, k, v, attention_fn, mesh):
        return (resolve_attention(q, k, v, config.attention, attention_fn, mesh,
                                  sm_scale=config.attention_multiplier),)

    def attention_out(x, o, layer, rng):
        del rng  # no dropout
        with jax.named_scope("attn_out"):
            mixed = jnp.einsum("bnsh,nhd->bsd", o.astype(cdt), layer["wo"].astype(cdt))
        return finish(x, mixed, layer)

    def scan(c, b, xs, dt, a_log, d, attention_fn, mesh):
        del attention_fn  # the attention layers'
        if mesh is not None and int(mesh.shape.get("pipeline", 1)) > 1:
            mesh = None  # as `resolve_attention`: no second shard_map inside the pipeline's region
        with jax.named_scope("ssd"):
            return (ssd.ssd(xs, b, c, dt, a_log, d, mesh=mesh, chunk=config.ssd_chunk),)

    def mamba_out_part(x, o, layer, rng):
        del rng
        return finish(x, mamba_out(x, o, layer, config), layer)

    if mesh is not None and int(mesh.shape.get("pipeline", 1)) > 1:
        mesh = None  # as `scan`
    return {MAMBA: (lambda x, layer: mamba_inputs(x, layer, config, mesh), mamba_out_part, scan),
            ATTENTION: (attention_qkv, attention_out, attend)}


def pattern(config: GraniteHybridConfig, mesh=None) -> Pattern:
    period = config.period
    return Pattern(_kinds(config, mesh), period, config.n_layer // len(period))


def _embedded(params, tokens, config: GraniteHybridConfig):
    """x_0 (B, S, D) in the compute dtype: the table's rows times `embedding_multiplier`."""
    with jax.named_scope("embed"):
        return (params["embed"][tokens] * config.embedding_multiplier).astype(config.dtype)


def forward(
    params: Dict[str, Any],
    tokens,  # (B, S) int32
    config: GraniteHybridConfig,
    attention_fn: Optional[Callable] = None,
    dropout_rng=None,  # accepted for API parity; no dropout
    mesh=None,
    num_microbatches: Optional[int] = None,
    return_aux: bool = False,
):
    """Logits (B, S, vocab) f32 against the tied table, over `logits_scaling`; with `return_aux`, also None: the
    model has no auxiliary loss."""
    del dropout_rng
    cdt = config.dtype
    x = _embedded(params, tokens, config)
    x, _ = apply_stack(params["blocks"], x, config, pattern=pattern(config, mesh), attention_fn=attention_fn,
                       mesh=mesh, num_microbatches=num_microbatches)
    logits = lm_head(x, lambda x: rms_norm(x, params["final_norm"], config.norm_eps), params["embed"], cdt)
    with jax.named_scope("head"):
        logits = logits / config.logits_scaling
    return (logits, None) if return_aux else logits


# Mean next-token cross entropy: `stack.lm_loss`'s arguments after `forward`.
loss_fn = functools.partial(lm_loss, forward)


def first_state(params: Dict[str, Any], tokens, config: GraniteHybridConfig):
    """The first mamba layer's states (B, H, N, P) f32 after the rows' last position, as the scan's own forward pass
    hands a state on: for the benchmark's `check`, which holds the reference's beside them."""
    assert config.layer_types[0] == MAMBA, "the first layer's input is the embedding"
    layer = jax.tree.map(lambda a: a[0], params["blocks"]["period"][0])
    _, b, xs, dt, a_log, _ = mamba_inputs(_embedded(params, tokens, config), layer, config)
    return ssd.state_after(xs, b, dt, a_log, chunk=config.ssd_chunk)
