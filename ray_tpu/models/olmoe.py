"""OLMoE (Muennighoff et al. 2024, arXiv:2409.02060): a pre-norm transformer
whose every feed-forward is a token-choice, dropless mixture of SwiGLU
experts, with rotary positions, RMSNorm, and q and k RMS-normed over the whole
projection before the split into heads.

Built from what the zoo has: RMSNorm and the rotary tables are `llama.py`'s,
the block's skeleton (remat, attention dispatch, scan, head, loss) is
`stack.py`'s, and the expert layer is `moe.moe_mlp`. The load-balancing loss and the router z-loss
of the paper are added to the loss with `aux_loss_weight` and `z_loss_weight`.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import apply_rope, rms_norm, rope_tables
from ray_tpu.models.moe import init_moe_params, moe_mlp, moe_param_logical_axes, routing_report
from ray_tpu.models.stack import apply_stack, block, lm_head, lm_loss


@dataclasses.dataclass(frozen=True)
class OLMoEConfig:
    """Defaults are OLMoE-1B-7B's published sizes."""

    vocab_size: int = 50304
    n_layer: int = 16
    n_head: int = 16
    n_kv_head: int = 16
    d_model: int = 2048
    d_expert: int = 1024  # width of one expert (the source's `intermediate_size`)
    n_experts: int = 64
    experts_per_token: int = 8
    norm_topk_prob: bool = False
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    aux_loss_weight: float = 0.01
    z_loss_weight: float = 0.001
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    remat_policy: Optional[str] = "save_attn"  # as LlamaConfig's
    attention: str = "auto"  # auto | flash | xla

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_head == 0
        return self.d_model // self.n_head

    @property
    def group_size(self) -> int:
        assert self.n_head % self.n_kv_head == 0
        return self.n_head // self.n_kv_head

    @classmethod
    def nano(cls, **kw):
        """Tiny config for CPU tests: 8 experts, 2 a token."""
        kw.setdefault("vocab_size", 256)
        kw.setdefault("max_seq_len", 64)
        return cls(n_layer=2, n_head=4, n_kv_head=4, d_model=64, d_expert=32,
                   n_experts=8, experts_per_token=2, **kw)


def num_params(config: OLMoEConfig) -> int:
    d, kvd = config.d_model, config.n_kv_head * config.head_dim
    per_layer = (
        2 * d * d + 2 * d * kvd                        # wq, wo, wk, wv
        + d * config.n_experts                         # router
        + 3 * config.n_experts * d * config.d_expert   # gate, up, down of every expert
        + 3 * d + kvd                                  # attn_norm, mlp_norm, q_norm; k_norm
    )
    return 2 * config.vocab_size * d + config.n_layer * per_layer + d


def train_flops_per_token(config: OLMoEConfig, seq_len: int) -> float:
    """6 FLOPs per matmul parameter a token meets (its `experts_per_token`
    experts, not all of them) plus full-square attention, as `gpt.py` counts."""
    d, kvd = config.d_model, config.n_kv_head * config.head_dim
    active = config.n_layer * (
        2 * d * d + 2 * d * kvd + d * config.n_experts
        + 3 * config.experts_per_token * d * config.d_expert
    ) + config.vocab_size * d
    return 6.0 * active + 12.0 * config.n_layer * d * seq_len


# --------------------------------------------------------------------------- init
def init_params(config: OLMoEConfig, key) -> Dict[str, Any]:
    d, L, V = config.d_model, config.n_layer, config.vocab_size
    nh, nkv, hd = config.n_head, config.n_kv_head, config.head_dim
    k = iter(jax.random.split(key, 8))
    std = 0.02
    out_std = std / math.sqrt(2 * L)
    pd = config.param_dtype

    def norm(key, shape, s):
        return (jax.random.normal(key, shape) * s).astype(pd)

    return {
        "embed": norm(next(k), (V, d), std),
        "blocks": {
            "attn_norm": jnp.ones((L, d), pd),
            "wq": norm(next(k), (L, d, nh, hd), std),
            "wk": norm(next(k), (L, d, nkv, hd), std),
            "wv": norm(next(k), (L, d, nkv, hd), std),
            "q_norm": jnp.ones((L, nh * hd), pd),
            "k_norm": jnp.ones((L, nkv * hd), pd),
            "wo": norm(next(k), (L, nh, hd, d), out_std),
            "mlp_norm": jnp.ones((L, d), pd),
            "moe": init_moe_params(next(k), L, d, config.d_expert, config.n_experts, pd),
        },
        "final_norm": jnp.ones((d,), pd),
        "lm_head": norm(next(k), (V, d), std),
    }


def param_logical_axes(config: OLMoEConfig) -> Dict[str, Any]:
    return {
        "embed": ("vocab", "embed"),
        "blocks": {
            "attn_norm": ("layers", None),
            "wq": ("layers", "embed", "heads", None),
            "wk": ("layers", "embed", "kv_heads", None),
            "wv": ("layers", "embed", "kv_heads", None),
            "q_norm": ("layers", None),
            "k_norm": ("layers", None),
            "wo": ("layers", "heads", None, "embed"),
            "mlp_norm": ("layers", None),
            "moe": moe_param_logical_axes(),
        },
        "final_norm": (None,),
        "lm_head": ("vocab", "embed"),
    }


# --------------------------------------------------------------------------- forward
def _parts(config: OLMoEConfig):
    """The two halves of one block on either side of attention. x: (B, S, D);
    cos/sin: this rank's rows of the rotary tables. `out_moe_part` returns
    (x, aux), aux what `route` reports for this layer. The scope names are
    read from the compiled program's `op_name`s (PERF.md, "names")."""
    cdt = config.dtype
    nh, nkv, hd = config.n_head, config.n_kv_head, config.head_dim

    def qkv_part(x, layer, cos, sin):
        B, S, _ = x.shape
        h = rms_norm(x, layer["attn_norm"], config.norm_eps).astype(cdt)
        q = jnp.einsum("bsd,dnh->bsnh", h, layer["wq"].astype(cdt)).reshape(B, S, nh * hd)
        k = jnp.einsum("bsd,dnh->bsnh", h, layer["wk"].astype(cdt)).reshape(B, S, nkv * hd)
        v = jnp.einsum("bsd,dnh->bnsh", h, layer["wv"].astype(cdt))
        # QK-norm over the whole projection, before the split into heads.
        q = rms_norm(q, layer["q_norm"], config.norm_eps).astype(cdt)
        k = rms_norm(k, layer["k_norm"], config.norm_eps).astype(cdt)
        q = apply_rope(q.reshape(B, S, nh, hd).transpose(0, 2, 1, 3), cos, sin)
        k = apply_rope(k.reshape(B, S, nkv, hd).transpose(0, 2, 1, 3), cos, sin)
        if config.group_size > 1:
            k = jnp.repeat(k, config.group_size, axis=1)
            v = jnp.repeat(v, config.group_size, axis=1)
        return q, k, v

    def out_moe_part(x, o, layer, rng):
        del rng  # no dropout
        with jax.named_scope("attn_out"):
            x = x + jnp.einsum("bnsh,nhd->bsd", o.astype(cdt), layer["wo"].astype(cdt))
        with jax.named_scope("moe"):
            h = rms_norm(x, layer["mlp_norm"], config.norm_eps).astype(cdt)
            moe = layer["moe"]
            h, aux = moe_mlp(
                h, moe["router_w"], moe["w_gate"], moe["w_up"], moe["w_down"],
                k=config.experts_per_token, norm_topk_prob=config.norm_topk_prob,
            )
            return x + h, aux

    return qkv_part, out_moe_part


def _aux_loss(aux, config: OLMoEConfig):
    return config.aux_loss_weight * aux["load_balance"] + config.z_loss_weight * aux["z"]


def forward(
    params: Dict[str, Any],
    tokens,  # (B, S) int32
    config: OLMoEConfig,
    attention_fn: Optional[Callable] = None,
    dropout_rng=None,  # accepted for API parity; no dropout
    mesh=None,
    num_microbatches: Optional[int] = None,
    return_aux: bool = False,
):
    """Logits (B, S, vocab) f32; with `return_aux`, also the weighted sum over
    the layers of the two auxiliary losses."""
    del dropout_rng
    cdt = config.dtype
    with jax.named_scope("embed"):
        x = params["embed"].astype(cdt)[tokens]
    qkv_part, out_moe_part = _parts(config)

    def out_part(x, o, layer, rng):
        x, aux = out_moe_part(x, o, layer, rng)
        return x, _aux_loss(aux, config)

    x, aux = apply_stack(
        params["blocks"],
        x,
        config,
        qkv_part,
        out_part,
        attention_fn=attention_fn,
        mesh=mesh,
        num_microbatches=num_microbatches,
        seq_streams=rope_tables(tokens.shape[1], config.head_dim, config.rope_theta),
    )
    logits = lm_head(
        x, lambda x: rms_norm(x, params["final_norm"], config.norm_eps), params["lm_head"], cdt
    )
    if return_aux:
        return logits, aux
    return logits


# Mean next-token cross entropy plus the two auxiliary losses of every layer:
# `stack.lm_loss`'s arguments after `forward`.
loss_fn = functools.partial(lm_loss, forward)


def routing_stats(params: Dict[str, Any], tokens, config: OLMoEConfig) -> Dict[str, Any]:
    """What the router did with `tokens` (B, S), per layer (leading axis):
    `experts` (L, B * S, k), each token's choices, `tokens_per_expert` (L, E),
    `load_max_over_mean` (L,), the two auxiliary
    terms `load_balance` and `z` (L,), and `dropped` (L,): the (token, expert)
    pairs, `B * S * experts_per_token`, less the rows the experts processed
    (`moe_mlp`'s count). The layer is dropless, so `dropped` is 0; it is
    counted, not assumed."""
    x = params["embed"].astype(config.dtype)[tokens]
    streams = rope_tables(tokens.shape[1], config.head_dim, config.rope_theta)
    pairs = tokens.size * config.experts_per_token
    parts = _parts(config)

    def layer_stats(x, layer):
        x, aux = block(x, layer, config, *parts, streams=streams)
        report = routing_report(aux, pairs)  # the layer holds every expert: its `held_pairs` are all the pairs
        return x, {**{name: report[name] for name in ("experts", "tokens_per_expert", "load_max_over_mean", "dropped")},
                   "load_balance": aux["load_balance"], "z": aux["z"]}

    return jax.lax.scan(layer_stats, x, params["blocks"])[1]
