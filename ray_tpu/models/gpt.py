"""GPT-2 family, TPU-first: the flagship model for the Train/bench path
(BASELINE.md north star: data-parallel GPT-2 at >=40% MFU).

Design choices for the MXU/XLA:
 - params are a plain pytree with per-leaf *logical* axis names; placement is
   decided by `parallel.ShardingRules` at trainer level (DP/FSDP/TP without
   touching the model).
 - per-layer params are stacked on a leading "layers" dim and the forward scans
   over them (`lax.scan`): compile time is O(1) in depth, and remat
   (`jax.checkpoint`) wraps the scanned block to trade FLOPs for HBM.
 - activations/matmuls in bfloat16, params & softmax/logits in float32.
 - the attention weights are stored as the matrices they are, in HF's own
   orientation: `qkv_w` (L, d, 3*nh*hd) with columns q heads | k heads | v heads
   (`c_attn.weight`), `out_w` (L, nh*hd, d) (`c_proj.weight`); `qkv_b` is
   (L, 3, nh, hd). A minor dimension that is a multiple of 128 is what the
   cast, the FSDP all-gather and the optimizer move as dense tiles: on several
   devices the block asks for the matrix whole and only then views it per head
   (`_whole_over`); on one it multiplies `qkv_w` as stored. A tree saved with
   the older (L, d, 3, nh, hd) / (L, nh, hd, d) shapes loads through
   `stored_form`.
 - attention: pallas flash kernel on TPU (partitioned over the mesh's batch and
   head axes), plain XLA elsewhere, ring attention (context parallelism)
   injectable via `attention_fn`.
 - vocab padded to a multiple of 128 so the logits matmul tiles the MXU.

The reference has no model code (it is the distributed substrate); the
equivalent user-facing artifact is its GPT-2 release benchmark
(`/root/reference/release/air_tests/air_benchmarks/` HF-GPT-2 workloads).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from ray_tpu.models.stack import apply_stack, lm_head, lm_loss


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304  # 50257 padded up to a multiple of 128
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    d_ff: int = 0  # 0 -> 4 * d_model
    max_seq_len: int = 1024
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    # None recomputes everything in the block; "dots" saves matmul outputs
    # across the remat boundary (less recompute, more memory); "save_attn"
    # remats the projections/MLP but keeps attention OUT of the remat region,
    # so the flash kernel (the most expensive op per byte saved) never
    # recomputes — q/k/v/o/lse are stored instead (~100MB/layer at B=16
    # S=1024 d=768 bf16).
    remat_policy: Optional[str] = "save_attn"
    attention: str = "auto"  # auto | flash | xla
    # Applied to embeddings and both residual branches when a dropout_rng is
    # passed to forward()/loss_fn (GPT-2 used 0.1; modern pretraining uses 0).
    dropout: float = 0.0
    # Mixture-of-experts: >0 replaces every block's dense MLP with a Switch
    # (top-1, dropless) layer of this many SwiGLU experts, sharded over the
    # `expert` mesh axis (models/moe.py; models/olmoe.py is the top-k model).
    # 0 = dense.
    moe_experts: int = 0
    moe_aux_weight: float = 0.01

    @property
    def ff_dim(self) -> int:
        return self.d_ff or 4 * self.d_model

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_head == 0
        return self.d_model // self.n_head

    # ---- presets ----
    @classmethod
    def gpt2_small(cls, **kw):
        return cls(n_layer=12, n_head=12, d_model=768, **kw)

    @classmethod
    def gpt2_medium(cls, **kw):
        return cls(n_layer=24, n_head=16, d_model=1024, **kw)

    @classmethod
    def gpt2_large(cls, **kw):
        return cls(n_layer=36, n_head=20, d_model=1280, **kw)

    @classmethod
    def gpt2_xl(cls, **kw):
        return cls(n_layer=48, n_head=25, d_model=1600, **kw)

    @classmethod
    def nano(cls, **kw):
        """Tiny config for CPU tests."""
        kw.setdefault("vocab_size", 256)
        kw.setdefault("max_seq_len", 128)
        return cls(n_layer=2, n_head=2, d_model=64, **kw)


def num_params(config: GPTConfig) -> int:
    d, L, V, F = config.d_model, config.n_layer, config.vocab_size, config.ff_dim
    E = config.moe_experts
    if E:
        mlp = d * E + E * 3 * d * F  # router + per-expert SwiGLU (gate, up, down)
    else:
        mlp = d * F + F + F * d + d
    per_layer = (
        3 * d * d + 3 * d  # qkv
        + d * d + d        # attn out
        + mlp
        + 4 * d            # 2 layernorms
    )
    return V * d + config.max_seq_len * d + L * per_layer + 2 * d


def train_flops_per_token(config: GPTConfig, seq_len: int) -> float:
    """6*N matmul flops + attention term, the standard MFU accounting.

    The tied wte is counted once: as embedding table it costs no matmul flops,
    as the logits head it does — num_params already includes it exactly once.
    """
    attn = 12 * config.n_layer * config.d_model * seq_len  # fwd+bwd qk+pv
    return 6.0 * num_params(config) + attn


# --------------------------------------------------------------------------- init
def init_params(config: GPTConfig, key) -> Dict[str, Any]:
    d, L, V, F = config.d_model, config.n_layer, config.vocab_size, config.ff_dim
    nh, hd = config.n_head, config.head_dim
    k = iter(jax.random.split(key, 16))
    std = 0.02
    proj_std = std / math.sqrt(2 * L)  # GPT-2 residual-scaled init
    pd = config.param_dtype

    def norm(key, shape, s):
        return (jax.random.normal(key, shape) * s).astype(pd)

    blocks = {
        "ln1_scale": jnp.ones((L, d), pd),
        "ln1_bias": jnp.zeros((L, d), pd),
        "qkv_w": norm(next(k), (L, d, 3 * nh * hd), std),
        "qkv_b": jnp.zeros((L, 3, nh, hd), pd),
        "out_w": norm(next(k), (L, nh * hd, d), proj_std),
        "out_b": jnp.zeros((L, d), pd),
        "ln2_scale": jnp.ones((L, d), pd),
        "ln2_bias": jnp.zeros((L, d), pd),
    }
    if config.moe_experts:
        from ray_tpu.models.moe import init_moe_params

        blocks["moe"] = init_moe_params(
            next(k), L, d, F, config.moe_experts, pd
        )
    else:
        blocks.update(
            {
                "fc_w": norm(next(k), (L, d, F), std),
                "fc_b": jnp.zeros((L, F), pd),
                "proj_w": norm(next(k), (L, F, d), proj_std),
                "proj_b": jnp.zeros((L, d), pd),
            }
        )
    params = {
        "wte": norm(next(k), (V, d), std),
        "wpe": norm(next(k), (config.max_seq_len, d), std),
        "blocks": blocks,
        "lnf_scale": jnp.ones((d,), pd),
        "lnf_bias": jnp.zeros((d,), pd),
    }
    return params


def param_logical_axes(config: GPTConfig) -> Dict[str, Any]:
    """Per-leaf logical axis names, consumed by parallel.ShardingRules."""
    blocks = {
        "ln1_scale": ("layers", None),
        "ln1_bias": ("layers", None),
        "qkv_w": ("layers", "embed", None),
        "qkv_b": ("layers", None, "heads", None),
        "out_w": ("layers", "heads", "embed"),
        "out_b": ("layers", None),
        "ln2_scale": ("layers", None),
        "ln2_bias": ("layers", None),
    }
    if config.moe_experts:
        from ray_tpu.models.moe import moe_param_logical_axes

        blocks["moe"] = moe_param_logical_axes()
    else:
        blocks.update(
            {
                "fc_w": ("layers", "embed", "mlp"),
                "fc_b": ("layers", "mlp"),
                "proj_w": ("layers", "mlp", "embed"),
                "proj_b": ("layers", None),
            }
        )
    return {
        "wte": ("vocab", "embed"),
        "wpe": (None, "embed"),
        "blocks": blocks,
        "lnf_scale": (None,),
        "lnf_bias": (None,),
    }


def stored_form(tree: Dict[str, Any]) -> Dict[str, Any]:
    """`tree` (parameters, or anything laid out like them: gradients, AdamW
    moments) with the attention weights as `init_params` stores them. A tree
    saved before PR 30 holds `qkv_w` (L, d, 3, nh, hd) and `out_w`
    (L, nh, hd, d): the same numbers in the same order, so loading is a
    reshape, decided from the array's rank alone. Anything else passes through."""
    blocks = dict(tree["blocks"])
    qkv_w, out_w = blocks["qkv_w"], blocks["out_w"]
    if qkv_w.ndim == 5:
        blocks["qkv_w"] = qkv_w.reshape(*qkv_w.shape[:2], -1)
    if out_w.ndim == 4:
        blocks["out_w"] = out_w.reshape(out_w.shape[0], -1, out_w.shape[-1])
    return {**tree, "blocks": blocks}


# --------------------------------------------------------------------------- forward
def _layer_norm(x, scale, bias, eps=1e-5):
    x = x.astype(jnp.float32)
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps) * scale + bias)




def _dropout(x, rate: float, rng):
    if rng is None or rate <= 0.0:
        return x
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0).astype(x.dtype)


def _gathers(mesh) -> bool:
    """Whether a block's weights may have to be gathered: several devices, and
    not the pipeline's manual region (as `resolve_attention`)."""
    return mesh is not None and mesh.size > 1 and int(mesh.shape.get("pipeline", 1)) == 1


def _whole_over(w, dim: int, mesh):
    """A block's weight matrix, cast to the compute dtype, with dimension `dim`
    (the one FSDP shards) whole on every device and the other left to the
    partitioner. Asked for on the matrix, before the heads are split out of
    it, this is where XLA puts the all-gather: the tiles that cross ICI are
    the matrix's own, 128-aligned and dense. Left to itself XLA gathers after
    the split, with head_dim (64: half of every (8, 128) tile is padding) as
    the minor dimension (PERF.md, PR 30)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = [P.UNCONSTRAINED] * w.ndim
    spec[dim] = None
    return jax.lax.with_sharding_constraint(w, NamedSharding(mesh, P(*spec)))


def _parts(config: GPTConfig, mesh):
    """The two halves of one block on either side of attention, as
    `stack.apply_stack` takes them. x: (B, S, D) in config.dtype; `out_mlp_part`
    returns (x, aux), aux the MoE load-balance loss (0.0 when dense)."""
    cdt = config.dtype
    nh, hd = config.n_head, config.head_dim

    def qkv_part(x, layer):
        h = _layer_norm(x, layer["ln1_scale"], layer["ln1_bias"]).astype(cdt)
        qkv_w = layer["qkv_w"].astype(cdt)
        if _gathers(mesh):
            # The matrix whole, then the product per head: q, k, v cross the
            # scan in the dense transposed layout XLA picks for this form.
            qkv_w = _whole_over(qkv_w, 0, mesh).reshape(-1, 3, nh, hd)
            qkv = jnp.einsum("bsd,dcnh->bscnh", h, qkv_w)
        else:
            # Nothing to gather: the matrix as it is stored. Viewed per head
            # it would be transposed every step (0.14 ms a layer on
            # gpt2-medium), which the per-head storage had for free.
            qkv = jnp.einsum("bsd,de->bse", h, qkv_w).reshape(*h.shape[:2], 3, nh, hd)
        qkv = qkv + layer["qkv_b"].astype(cdt)
        return tuple(jnp.moveaxis(qkv[:, :, i], 2, 1) for i in range(3))  # (B, nh, S, hd)

    def out_mlp_part(x, o, layer, rng):
        with jax.named_scope("out_mlp"):
            r1, r2 = (None, None) if rng is None else jax.random.split(rng)
            out_w = layer["out_w"].astype(cdt)
            if _gathers(mesh):
                out_w = _whole_over(out_w, 1, mesh)
            out_w = out_w.reshape(nh, hd, -1)  # rows are head-major: a view
            o = jnp.einsum("bnsh,nhd->bsd", o.astype(cdt), out_w) + layer["out_b"].astype(cdt)
            x = x + _dropout(o, config.dropout, r1)

            h = _layer_norm(x, layer["ln2_scale"], layer["ln2_bias"]).astype(cdt)
            aux = jnp.zeros((), jnp.float32)
            if config.moe_experts:
                from ray_tpu.models.moe import moe_mlp

                moe = layer["moe"]
                h, moe_aux = moe_mlp(
                    h, moe["router_w"], moe["w_gate"], moe["w_up"], moe["w_down"], k=1
                )
                aux = moe_aux["load_balance"]
            else:
                h = jnp.einsum("bsd,df->bsf", h, layer["fc_w"].astype(cdt)) + layer["fc_b"].astype(cdt)
                h = jax.nn.gelu(h)
                h = jnp.einsum("bsf,fd->bsd", h, layer["proj_w"].astype(cdt)) + layer["proj_b"].astype(cdt)
            return x + _dropout(h, config.dropout, r2), aux

    return qkv_part, out_mlp_part


def forward(
    params: Dict[str, Any],
    tokens,  # (B, S) int32
    config: GPTConfig,
    attention_fn: Optional[Callable] = None,
    dropout_rng=None,
    mesh=None,
    num_microbatches: Optional[int] = None,
    return_aux: bool = False,
):
    """Returns logits (B, S, vocab) in float32 (with `return_aux`, a
    (logits, aux) pair: the weighted MoE load-balance loss, None when dense).
    Pass dropout_rng to enable dropout (training); omit it for deterministic
    eval.

    With a mesh whose `pipeline` axis is >1, the layer stack runs as a GPipe
    microbatch pipeline (`parallel.pipeline`): each stage group holds
    n_layer/pipeline layers, activations ppermute between stages over ICI.
    Embedding and LM head stay outside the pipeline (replicated over the
    pipeline axis — they are a small fraction of the FLOPs)."""
    B, S = tokens.shape
    cdt = config.dtype
    with jax.named_scope("embed"):
        x = params["wte"].astype(cdt)[tokens] + params["wpe"].astype(cdt)[:S][None]
    layers_rng = None
    if dropout_rng is not None and config.dropout > 0:
        emb_rng, layers_rng = jax.random.split(dropout_rng)
        x = _dropout(x, config.dropout, emb_rng)

    x, moe_aux = apply_stack(
        params["blocks"],
        x,
        config,
        *_parts(config, mesh),
        attention_fn=attention_fn,
        mesh=mesh,
        num_microbatches=num_microbatches,
        layers_rng=layers_rng,
    )
    # Tied LM head.
    logits = lm_head(
        x, lambda x: _layer_norm(x, params["lnf_scale"], params["lnf_bias"]), params["wte"], cdt
    )
    if return_aux:
        return logits, (config.moe_aux_weight * moe_aux if config.moe_experts else None)
    return logits


# Causal LM cross entropy (mean over tokens), plus the weighted MoE load-balance
# loss under `moe_experts`: `stack.lm_loss`'s arguments after `forward`.
loss_fn = functools.partial(lm_loss, forward)
