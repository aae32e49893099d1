"""Xing4.0 (XingChen-AGI, `model_type` `xing4_0`; the published sizes are
Xing4.0-29B-A4B's): GLM-4 MoE Lite's block (latent attention, a leading dense
SwiGLU, then token-choice experts beside a shared one, a sigmoid router with a
selection bias) on a residual path of `hc_mult` streams, mixed round every
sublayer by manifold-constrained hyper-connections (`ops/hyper_connections.py`
has the equations), with keys 192 and values 128 wide and a YaRN rotation.

    in:       every stream is the token's embedding;  out: the streams' sum, the final norm, the head
    sublayer: (H_pre, H_post, H_res) = maps(X);  u = sum_i H_pre[i] X[i];  y = F(N(u))
              X'[i] = H_post[i] y + sum_j H_res[i, j] X[j]        F: attention, then the feed-forward
    latent:   as `glm4_moe_lite.py`'s, the 64 rotary columns by YaRN's frequencies (factor 64 over 4,096),
              causal softmax at 192^-1/2 x (0.1 ln 64 + 1)^2, values and output 128 wide

The sublayers are `glm4_moe_lite.py`'s own functions (`latent_qkv`,
`attention_out`, `dense_ffn`, `moe_ffn`), the parameters its tree with two
`hc_*` groups a layer, the stack `stack.py`'s with the streams (B, n, S, D) as
its carry. Under "save_attn" the maps of the attention sublayer cross the
attention call beside q, k, v (96 B a token), the feed-forward's are made and
used inside `out_part`'s checkpoint: what the scan stacks a layer is the
streams (in `dtype`), q, k, v, o and those two maps. `attend` is the kind's
own because the scale of the scores is the model's. The prediction module
(`num_nextn_predict_layers` 1 in the source) is not written over streams.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import glm4_moe_lite as glm
from ray_tpu.models.glm4_moe_lite import DENSE, MOE, GLM4MoELiteConfig, layer_kinds  # noqa: F401
from ray_tpu.models.llama import Yarn, rms_norm, rope_tables
from ray_tpu.models.moe import routing_report
from ray_tpu.models.stack import Pattern, apply_stack, block, lm_head, lm_loss, resolve_attention
from ray_tpu.ops import hyper_connections as mhc


@dataclasses.dataclass(frozen=True)
class Xing4Config(GLM4MoELiteConfig):
    """Defaults are Xing4.0-29B-A4B's published sizes (the source's key where the name differs)."""

    vocab_size: int = 131072
    n_layer: int = 40
    n_dense_layers: int = 2
    n_head: int = 32
    d_model: int = 3584
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    d_ff: int = 9216
    d_expert: int = 1024
    routed_scaling_factor: float = 2.0
    n_predict_layers: int = 0  # the source's one module lies on the pipeline's last stage and is not written here
    max_seq_len: int = 262144
    rope_theta: float = 1e4
    norm_eps: float = 1e-6
    yarn: Optional[Yarn] = Yarn(factor=64.0, original_max_position_embeddings=4096, beta_fast=32.0, beta_slow=1.0,
                                mscale=1.0, mscale_all_dim=1.0)
    hc_mult: int = 4  # the streams of the residual path
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: Tuple[float, float] = (-30.0, 30.0)  # mhc_h_res_clamp_min / max
    hc_phi_std: float = 0.01  # Phi's init: `m`'s entries then have a variance near 1.4 at d_model x hc_mult = 14,336

    def __post_init__(self):
        assert self.n_predict_layers == 0, "the prediction module over several streams is not written"

    @property
    def softmax_scale(self) -> float:
        return self.head_dim ** -0.5 * (self.yarn.softmax_scale if self.yarn else 1.0)

    @classmethod
    def nano(cls, **kw):
        """Tiny config for CPU tests: one dense and two expert layers, 8 experts of which this share holds 2, 2 a
        token, four streams, keys of 32 and values of 16, a rotation scaled by 4 over 16 positions."""
        kw.setdefault("vocab_size", 256)
        kw.setdefault("max_seq_len", 64)
        kw.setdefault("n_experts_held", 2)
        kw.setdefault("first_expert_held", 2)
        kw.setdefault("n_layer", 3)
        kw.setdefault("yarn", Yarn(factor=4.0, original_max_position_embeddings=16, mscale_all_dim=1.0))
        kw.setdefault("hc_phi_std", 0.06)  # 1 / sqrt(4 x 64) = 0.0625
        return cls(n_dense_layers=1, n_head=4, d_model=64, q_lora_rank=32, kv_lora_rank=16,
                   qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16, d_ff=160, d_expert=32,
                   n_experts=8, experts_per_token=2, **kw)


# --------------------------------------------------------------------------- sizes
def _stream_params(config: Xing4Config) -> int:
    """One sublayer's maps: Phi, three scales, the biases."""
    columns = mhc.n_maps(config.hc_mult)
    return columns * config.hc_mult * config.d_model + 3 + columns


def num_params(config: Xing4Config) -> int:
    """Of this share, as `glm4_moe_lite.num_params`, and two sublayers' maps a layer."""
    return glm.num_params(config) + 2 * config.n_layer * _stream_params(config)


def train_flops_per_token(config: Xing4Config, seq_len: int) -> float:
    """`glm4_moe_lite.train_flops_per_token` (attention by both widths) plus 6 FLOPs for each entry of Phi, which
    every token meets in each of a layer's two sublayers. The mixes and the normalisations are no products."""
    phi = mhc.n_maps(config.hc_mult) * config.hc_mult * config.d_model
    return glm.train_flops_per_token(config, seq_len) + 6.0 * 2 * config.n_layer * phi


# --------------------------------------------------------------------------- init
def _layer_shapes(config: Xing4Config, kind: str):
    """`glm4_moe_lite._layer_shapes` and, for each of the layer's two sublayers, its maps' leaves
    (`ops/hyper_connections.maps` says what they hold). Phi's columns are its rows here: 24 rows of 14,336 f32 fill
    their tiles, 14,336 rows of 24 would each be padded to 128 lanes, five times the bytes in four copies."""
    n, columns = config.hc_mult, mhc.n_maps(config.hc_mult)
    maps = {"phi": ((columns, n, config.d_model), config.hc_phi_std, (None, None, "embed")),
            "alpha": ((3,), "ones", (None,)), "bias": ((columns,), 1.0, (None,))}
    return {**glm._layer_shapes(config, kind), "hc_attn": dict(maps), "hc_ffn": dict(maps)}


init_params = functools.partial(glm.init_params, layer_shapes=_layer_shapes)
param_logical_axes = functools.partial(glm.param_logical_axes, layer_shapes=_layer_shapes)
frozen_params = functools.partial(glm.frozen_params, layer_shapes=_layer_shapes)


# --------------------------------------------------------------------------- forward
def _kinds(config: Xing4Config, stats: bool = False, mesh=None):
    """`stack.Pattern.kinds`: (qkv_part, out_part, attend) of each kind of layer. x: the streams (B, n, S, D);
    cos/sin: this rank's rows of the rotary tables. An `out_part` returns (x, aux): a zero, or with `stats`
    `{"routing": what `moe_mlp` reports (an expert layer's), "res_sum_err": the largest distance from 1 of a row or
    column sum of either sublayer's H_res}`. `mesh`: `hidden`'s, for the parts that may hold a Mosaic call (the
    mixes' backward rules) and are handed no mesh by the stack."""
    maps = functools.partial(mhc.maps, norm_eps=config.norm_eps, rounds=config.hc_sinkhorn_iters,
                             eps=config.hc_eps, clamp=config.hc_clamp, mesh=mesh)
    post_res_mix = functools.partial(mhc.post_res_mix, mesh=mesh)

    def qkv_part(x, layer, cos, sin):
        h_pre, h_post, h_res = maps(x, **layer["hc_attn"])
        u = mhc.pre_mix(x, h_pre)
        with jax.named_scope("mla_latent"):
            q, k, v = glm.latent_qkv(u, layer, config, cos, sin)
        return q, k, v, h_post, h_res

    def attend(q, k, v, h_post, h_res, attention_fn, mesh):
        o = resolve_attention(q, k, v, config.attention, attention_fn, mesh, sm_scale=config.softmax_scale)
        return o, h_post, h_res

    def dense(u, layer):
        with jax.named_scope("dense_mlp"):
            return glm.dense_ffn(u, layer, config), None

    def moe(u, layer):
        with jax.named_scope("moe"):
            routed, shared, aux = glm.moe_ffn(u, layer, config)
            return routed + shared, aux

    def sum_err(h_res):
        return jnp.maximum(jnp.abs(h_res.sum(axis=0) - 1).max(), jnp.abs(h_res.sum(axis=1) - 1).max())

    def out_part(ffn):
        def part(x, o, layer, rng, h_post, h_res):
            del rng  # no dropout
            with jax.named_scope("attn_out"):
                y = glm.attention_out(o, layer, config)
            x = post_res_mix(x, y, h_post, h_res)
            err = sum_err(h_res) if stats else None
            h_pre, h_post, h_res = maps(x, **layer["hc_ffn"])
            y, aux = ffn(mhc.pre_mix(x, h_pre), layer)
            x = post_res_mix(x, y, h_post, h_res)
            if stats:
                return x, {"routing": aux, "res_sum_err": jnp.maximum(err, sum_err(h_res))}
            return x, jnp.zeros((), jnp.float32)
        return part

    return {DENSE: (qkv_part, out_part(dense), attend), MOE: (qkv_part, out_part(moe), attend)}


def pattern(config: Xing4Config, stats: bool = False, mesh=None) -> Pattern:
    return Pattern(_kinds(config, stats, mesh), (MOE,), config.n_layer - config.n_dense_layers,
                   (DENSE,) * config.n_dense_layers)


def _streams(seq_len: int, config: Xing4Config):
    return rope_tables(seq_len, config.qk_rope_head_dim, config.rope_theta, config.yarn)


def streams_in(params, tokens, config: Xing4Config):
    """The residual path's first value (B, n, S, D): every stream the token's embedding."""
    with jax.named_scope("embed"):
        x = params["embed"].astype(config.dtype)[tokens]
    with jax.named_scope("mhc"), jax.named_scope("in"):
        return jnp.broadcast_to(x[:, None], (x.shape[0], config.hc_mult, *x.shape[1:]))


def hidden(params, tokens, config: Xing4Config, attention_fn=None, mesh=None,
           num_microbatches: Optional[int] = None):
    """The streams' sum after the last layer (B, S, D) f32 for `tokens` (B, S), before the final norm."""
    x, _ = apply_stack(
        params["blocks"], streams_in(params, tokens, config), config, pattern=pattern(config, mesh=mesh),
        attention_fn=attention_fn, mesh=mesh, num_microbatches=num_microbatches,
        seq_streams=_streams(tokens.shape[1], config))
    with jax.named_scope("mhc"), jax.named_scope("out"):
        return x.astype(jnp.float32).sum(axis=1)


def forward(
    params: Dict[str, Any],
    tokens,  # (B, S) int32
    config: Xing4Config,
    attention_fn: Optional[Callable] = None,
    dropout_rng=None,  # accepted for API parity; no dropout
    mesh=None,
    num_microbatches: Optional[int] = None,
    return_aux: bool = False,
):
    """Logits (B, S, vocab) f32 against the head (untied); with `return_aux` also None: no auxiliary loss."""
    del dropout_rng
    x = hidden(params, tokens, config, attention_fn, mesh, num_microbatches)
    logits = lm_head(
        x, lambda x: rms_norm(x, params["final_norm"], config.norm_eps), params["lm_head"], config.dtype
    )
    return (logits, None) if return_aux else logits


loss_fn = functools.partial(lm_loss, forward)


def routing_stats(params: Dict[str, Any], tokens, config: Xing4Config) -> Dict[str, Any]:
    """What the routers did with `tokens` (B, S + 1), a batch's rows as `loss_fn` takes them, per expert layer
    (leading axis, in the published order), as `glm4_moe_lite.routing_stats`; and `res_sum_err` (every layer): the
    largest distance from 1 of a row or column sum of the layer's two H_res."""
    inputs = tokens[:, :-1]
    x = streams_in(params, inputs, config)
    streams = _streams(inputs.shape[1], config)
    pairs = inputs.size * config.experts_per_token
    walked = pattern(config, stats=True)
    per_layer, sum_errs = [], []
    for kind, layer in walked.layers(params["blocks"]):
        qkv_part, out_part, attend = walked.kinds[kind]
        x, aux = block(x, layer, config, qkv_part, out_part, streams=streams, attend=attend)
        sum_errs.append(aux["res_sum_err"])
        if aux["routing"] is not None:
            per_layer.append(routing_report(aux["routing"], pairs))
    return {**jax.tree.map(lambda *leaves: jnp.stack(leaves), *per_layer), "res_sum_err": jnp.stack(sum_errs)}
