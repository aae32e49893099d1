"""Llama-family transformer, TPU-first: RMSNorm, SwiGLU MLP, rotary position
embeddings, grouped-query attention, untied LM head.

Second model family of the zoo (same design rules as gpt.py): plain pytree
params with per-leaf logical axes, layers stacked + scanned (shared
`models/stack.py` scaffolding, so DP/FSDP/TP/PP/CP all compose exactly as for
GPT), bf16 matmuls with f32 norms/softmax/logits, pallas flash attention on
TPU with ring attention injectable for context parallelism.

The reference ships no model code; its user-facing analogue is the HF
workloads in `release/air_tests/air_benchmarks/` (e.g. Llama fine-tunes).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.stack import apply_stack, lm_head, lm_loss


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    n_layer: int = 32
    n_head: int = 32
    n_kv_head: int = 32  # < n_head = grouped-query attention
    d_model: int = 4096
    d_ff: int = 11008  # SwiGLU hidden dim (~8/3 * d, rounded to hardware-friendly)
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    # "save_attn" (default) remats the projections/MLP but keeps attention
    # outside the remat region (no kernel recompute in backward, q/k/v/o/lse
    # saved); "dots" saves matmul outputs across the block remat boundary;
    # None recomputes the whole block.
    remat_policy: Optional[str] = "save_attn"
    attention: str = "auto"  # auto | flash | xla

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_head == 0
        return self.d_model // self.n_head

    @property
    def group_size(self) -> int:
        assert self.n_head % self.n_kv_head == 0
        return self.n_head // self.n_kv_head

    # ---- presets ----
    @classmethod
    def llama2_7b(cls, **kw):
        return cls(**kw)

    @classmethod
    def llama2_13b(cls, **kw):
        return cls(n_layer=40, n_head=40, n_kv_head=40, d_model=5120, d_ff=13824, **kw)

    @classmethod
    def llama3_8b(cls, **kw):
        return cls(
            vocab_size=128256, n_layer=32, n_head=32, n_kv_head=8,
            d_model=4096, d_ff=14336, max_seq_len=8192, rope_theta=500000.0, **kw
        )

    @classmethod
    def nano(cls, **kw):
        """Tiny GQA config for CPU tests (2 kv heads for 4 q heads)."""
        kw.setdefault("vocab_size", 256)
        kw.setdefault("max_seq_len", 128)
        return cls(n_layer=2, n_head=4, n_kv_head=2, d_model=64, d_ff=128, **kw)


def num_params(config: LlamaConfig) -> int:
    d, L, V, F = config.d_model, config.n_layer, config.vocab_size, config.d_ff
    kvd = config.n_kv_head * config.head_dim
    per_layer = (
        d * d            # wq
        + 2 * d * kvd    # wk, wv
        + d * d          # wo
        + 2 * d * F      # w_gate, w_up
        + F * d          # w_down
        + 2 * d          # 2 rmsnorm scales
    )
    return 2 * V * d + L * per_layer + d  # embed + untied head + final norm


def train_flops_per_token(config: LlamaConfig, seq_len: int) -> float:
    attn = 12 * config.n_layer * config.d_model * seq_len
    return 6.0 * num_params(config) + attn


# --------------------------------------------------------------------------- init
def init_params(config: LlamaConfig, key) -> Dict[str, Any]:
    d, L, V, F = config.d_model, config.n_layer, config.vocab_size, config.d_ff
    nh, nkv, hd = config.n_head, config.n_kv_head, config.head_dim
    k = iter(jax.random.split(key, 16))
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
            "wo": norm(next(k), (L, nh, hd, d), out_std),
            "mlp_norm": jnp.ones((L, d), pd),
            "w_gate": norm(next(k), (L, d, F), std),
            "w_up": norm(next(k), (L, d, F), std),
            "w_down": norm(next(k), (L, F, d), out_std),
        },
        "final_norm": jnp.ones((d,), pd),
        "lm_head": norm(next(k), (V, d), std),
    }


def param_logical_axes(config: LlamaConfig) -> Dict[str, Any]:
    return {
        "embed": ("vocab", "embed"),
        "blocks": {
            "attn_norm": ("layers", None),
            "wq": ("layers", "embed", "heads", None),
            "wk": ("layers", "embed", "kv_heads", None),
            "wv": ("layers", "embed", "kv_heads", None),
            "wo": ("layers", "heads", None, "embed"),
            "mlp_norm": ("layers", None),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        },
        "final_norm": (None,),
        "lm_head": ("vocab", "embed"),
    }


# --------------------------------------------------------------------------- forward
def rms_norm(x, scale, eps):
    xf = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return xf * rms * scale


class Yarn(NamedTuple):
    """YaRN's scaling of the rotary frequencies (Peng et al., arXiv:2309.00071, as DeepSeek-V3's modelling code has
    it; the names are `rope_scaling`'s keys)."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @staticmethod
    def get_mscale(factor: float, mscale: float) -> float:
        return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0

    @property
    def table_scale(self) -> float:
        """What cos and sin are multiplied by."""
        return self.get_mscale(self.factor, self.mscale) / self.get_mscale(self.factor, self.mscale_all_dim)

    @property
    def softmax_scale(self) -> float:
        """What the softmax's `head_dim^-1/2` is multiplied by."""
        return self.get_mscale(self.factor, self.mscale_all_dim) ** 2

    def correction_range(self, dim: int, theta: float) -> Tuple[int, int]:
        """(low, high): the pairs of columns between which the ramp runs, those that turn `beta_fast` and
        `beta_slow` times over the original context."""
        def pair_of(rotations):
            return dim * math.log(self.original_max_position_embeddings / (rotations * 2 * math.pi)) / (2 * math.log(theta))
        return max(math.floor(pair_of(self.beta_fast)), 0), min(math.ceil(pair_of(self.beta_slow)), dim - 1)

    def frequencies(self, dim: int, theta: float):
        """(dim / 2,): the published frequencies (extrapolation) below `low`, those over `factor` (interpolation)
        above `high`, blended by a linear ramp between."""
        half = dim // 2
        extra = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
        low, high = self.correction_range(dim, theta)
        ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low) / ((high if high != low else high + 0.001) - low), 0, 1)
        return extra / self.factor * ramp + extra * (1 - ramp)


def rope_tables(seq_len: int, head_dim: int, theta: float, yarn: Optional[Yarn] = None):
    """Precomputed (S, head_dim/2) cos/sin tables with GLOBAL positions —
    computed once per forward and passed through the stack as sequence
    streams, so context-parallel shards rotate with their true positions (a
    locally-indexed arange inside the block would restart every CP shard at
    position 0) and the tables aren't rebuilt per layer under remat. With
    `yarn`, its frequencies and its scale of both tables."""
    half = head_dim // 2
    if yarn is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freqs = yarn.frequencies(head_dim, theta)
    angles = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if yarn is not None and yarn.table_scale != 1.0:
        cos, sin = cos * yarn.table_scale, sin * yarn.table_scale
    return cos, sin


def apply_rope(x, cos, sin):
    """Apply rotary embeddings. x: (B, H, S_local, hd); cos/sin: (S_local, hd/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    rx1 = x1 * cos - x2 * sin
    rx2 = x2 * cos + x1 * sin
    return jnp.concatenate([rx1, rx2], axis=-1).astype(x.dtype)


def _parts(config: LlamaConfig):
    """The two halves of one block on either side of attention, as
    `stack.apply_stack` takes them. x: (B, S, D); cos/sin: this rank's rows
    of the rotary tables."""
    cdt = config.dtype
    g = config.group_size

    def qkv_part(x, layer, cos, sin):
        h = rms_norm(x, layer["attn_norm"], config.norm_eps).astype(cdt)
        q = jnp.einsum("bsd,dnh->bnsh", h, layer["wq"].astype(cdt))
        k = jnp.einsum("bsd,dnh->bnsh", h, layer["wk"].astype(cdt))
        v = jnp.einsum("bsd,dnh->bnsh", h, layer["wv"].astype(cdt))
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        if g > 1:
            # GQA: each kv head serves `group_size` query heads.
            k = jnp.repeat(k, g, axis=1)
            v = jnp.repeat(v, g, axis=1)
        return q, k, v

    def out_mlp_part(x, o, layer, rng):
        del rng  # no dropout
        with jax.named_scope("out_mlp"):
            o = jnp.einsum("bnsh,nhd->bsd", o.astype(cdt), layer["wo"].astype(cdt))
            x = x + o

            h = rms_norm(x, layer["mlp_norm"], config.norm_eps).astype(cdt)
            gate = jnp.einsum("bsd,df->bsf", h, layer["w_gate"].astype(cdt))
            up = jnp.einsum("bsd,df->bsf", h, layer["w_up"].astype(cdt))
            h = jax.nn.silu(gate) * up
            h = jnp.einsum("bsf,fd->bsd", h, layer["w_down"].astype(cdt))
            return x + h, jnp.zeros((), jnp.float32)

    return qkv_part, out_mlp_part


def forward(
    params: Dict[str, Any],
    tokens,  # (B, S) int32
    config: LlamaConfig,
    attention_fn: Optional[Callable] = None,
    dropout_rng=None,  # accepted for API parity; Llama pretraining uses none
    mesh=None,
    num_microbatches: Optional[int] = None,
    return_aux: bool = False,
):
    """Logits (B, S, vocab) f32 (with `return_aux`, a (logits, None) pair: no
    auxiliary loss); pipelines over the `pipeline` mesh axis like GPT (shared
    stack scaffolding)."""
    del dropout_rng
    cdt = config.dtype
    with jax.named_scope("embed"):
        x = params["embed"].astype(cdt)[tokens]
    x, _ = apply_stack(
        params["blocks"],
        x,
        config,
        *_parts(config),
        attention_fn=attention_fn,
        mesh=mesh,
        num_microbatches=num_microbatches,
        seq_streams=rope_tables(tokens.shape[1], config.head_dim, config.rope_theta),
    )
    logits = lm_head(
        x, lambda x: rms_norm(x, params["final_norm"], config.norm_eps), params["lm_head"], cdt
    )
    if return_aux:
        return logits, None
    return logits


# Causal LM cross entropy (mean over tokens): `stack.lm_loss`'s arguments after `forward`.
loss_fn = functools.partial(lm_loss, forward)
