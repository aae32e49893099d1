"""Keye-VL-2.0's language model (Kwai-Keye, `model_type` `KeyeVL2`; the
published sizes are Keye-VL-2.0-30B-A3B's): a pre-norm stack of identical
layers, grouped-query attention over the keys a lightning indexer selects for
each query (`sa_config`: DeepSeek-V3.2-Exp's sparse attention at this model's
sizes) and a token-choice mixture of SwiGLU experts, with three-component
rotary positions. The vision tower is not here: tokens are text.

    N = RMSNorm;  h = N(x)
    indexer:   qI_tj = rope(h W_Iq)_j (16 heads of 64);  kI_s = rope(LN(h W_Ik));
               w_t = (h W_Iw) / sqrt(16 x 64);  I_ts = sum_j w_tj relu(qI_tj . kI_s), f32;
               it reads stop_gradient(h)
    selection: S_t = the keys s <= t whose I_ts is among the `index_topk` largest of
               query t's (ties kept; every key where there are no more); no gradient
    attention: q = rope(N_q(h W_q)) per head, k = rope(N_k(h W_k)), v = h W_v; query head a
               on key/value head a // group; softmax over S_t at head_dim^-1/2;  x <- x + o W_o
    experts:   s = softmax(N(x) W_r); the 8 largest, renormalised; x <- x + sum_e w_e E_e(N(x))
    indexer loss: P_ts = mean over heads of the attention's probabilities on S_t, no
               gradient;  L_I = mean_t KL(P_t || softmax_{S_t}(I_t.)), summed over layers
    loss = CE + `index_loss_weight` x L_I + `aux_loss_weight` x load balance

By the two stop_gradients the indexer's leaves learn from L_I alone and nothing
else learns from it. `rope` takes positions (3, B, S) and rotates a head's
frequency pairs in `mrope_section` runs by the first, second and third
component; text sets the three equal, which is plain rotary. The indexer
rotates all of its dimensions by the first component alone.

Built from what the zoo has: RMSNorm and the rotation are `llama.py`'s, the
block's skeleton is `stack.py`'s with the layer's own `attend` where the
attention dispatch stands (`stack.block`), the operators round the attention
call are `ops/lightning_indexer.py`'s, the attention itself
`flash_attention(keep=)` with its key/value heads unrepeated, and the layer
round them (projections with per-head QK-norm, output projection, the expert
layer `moe.moe_mlp` told which experts this chip holds, the parameter tree) is
`gqa_experts.py`'s, which `sdar.py` shares.
`index_topk` None is the model without `sa_config`: dense grouped-query
attention, no indexer.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import gqa_experts
from ray_tpu.models.gqa_experts import by_batch
from ray_tpu.models.llama import apply_rope, rms_norm
from ray_tpu.models.stack import apply_stack, block, lm_head, lm_loss


@dataclasses.dataclass(frozen=True)
class KeyeVL2Config:
    """Defaults are Keye-VL-2.0-30B-A3B's published sizes (the source's key where the name differs)."""

    vocab_size: int = 151936
    n_layer: int = 48  # num_hidden_layers
    n_head: int = 32
    n_kv_head: int = 4
    head_dim: int = 128
    d_model: int = 2048
    d_expert: int = 768  # moe_intermediate_size
    n_experts: int = 128  # the router's width (num_experts)
    experts_per_token: int = 8
    n_experts_held: Optional[int] = None  # experts computed here (None: all), ...
    first_expert_held: int = 0  # ... from this one on
    norm_topk_prob: bool = True
    index_n_heads: int = 16  # sa_config
    index_head_dim: int = 64
    index_topk: Optional[int] = 2048  # None: no sa_config, dense attention and no indexer
    index_loss_weight: float = 1.0
    mrope_section: Tuple[int, int, int] = (16, 24, 24)
    rope_theta: float = 1e7
    max_seq_len: int = 262144
    norm_eps: float = 1e-6
    aux_loss_weight: float = 0.001
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    remat_policy: Optional[str] = "save_attn"  # as LlamaConfig's; the selection is saved with q, k, v, o
    attention: str = "auto"  # auto | flash | xla

    def __post_init__(self):
        assert sum(self.mrope_section) == self.head_dim // 2, "the sections count a head's frequency pairs"
        assert self.n_head % self.n_kv_head == 0

    @property
    def held(self) -> int:
        return self.n_experts if self.n_experts_held is None else self.n_experts_held

    @property
    def sparse(self) -> bool:
        return self.index_topk is not None

    @classmethod
    def nano(cls, **kw):
        """Tiny config for CPU tests: 8 experts of which this share holds 4, 2 a
        token; 4 query heads on 2 key/value heads of 16; an indexer of 2 heads
        of 8 that selects 24 keys of a row of 64."""
        kw.setdefault("vocab_size", 256)
        kw.setdefault("max_seq_len", 64)
        kw.setdefault("n_experts_held", 4)
        kw.setdefault("first_expert_held", 2)
        kw.setdefault("n_layer", 2)
        kw.setdefault("index_topk", 24)
        return cls(n_head=4, n_kv_head=2, head_dim=16, d_model=64, d_expert=32, n_experts=8,
                   experts_per_token=2, index_n_heads=2, index_head_dim=8, mrope_section=(2, 3, 3), **kw)


# --------------------------------------------------------------------------- sizes
def _layer_shapes(config: KeyeVL2Config):
    """`gqa_experts.layer_shapes`, and the indexer's leaves where the configuration has one."""
    shapes = gqa_experts.layer_shapes(config)
    if config.sparse:
        d, hi, di = config.d_model, config.index_n_heads, config.index_head_dim
        shapes["indexer"] = {
            "wq": ((d, hi, di), 0.02, ("embed", None, None)),
            "wk": ((d, di), 0.02, ("embed", None)),
            "k_norm": ((di,), "ones", (None,)), "k_norm_bias": ((di,), "zeros", (None,)),
            "ww": ((d, hi), 0.02, ("embed", None)),
        }
    return shapes


def _indexer_matmul_params(config: KeyeVL2Config) -> int:
    if not config.sparse:
        return 0
    return config.d_model * (config.index_n_heads * (config.index_head_dim + 1) + config.index_head_dim)


def _matmul_params(config: KeyeVL2Config) -> int:
    """One layer's parameters that every token meets as an operand of a product."""
    return gqa_experts.matmul_params(config) + _indexer_matmul_params(config)


def num_params(config: KeyeVL2Config) -> int:
    """Of this share: the experts held, not all the router names; embedding and head untied."""
    d = config.d_model
    per_layer = (gqa_experts.layer_params(config) + _indexer_matmul_params(config)
                 + (2 * config.index_head_dim if config.sparse else 0))
    return 2 * config.vocab_size * d + d + config.n_layer * per_layer


def selected_pairs(seq_len: int, topk: Optional[int]) -> int:
    """(query, key) pairs of one head of one row that the selection keeps, ties
    apart: every pair of the causal half up to `topk` keys a query, `topk` from there on."""
    if topk is None or topk >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return topk * (topk + 1) // 2 + (seq_len - topk) * topk


def train_flops_per_token(config: KeyeVL2Config, seq_len: int) -> float:
    """The model's FLOPs, not the walk's: 6 per matmul parameter a token meets
    here (of its `experts_per_token` experts the share `held / n_experts`, in
    expectation); attention's two products forward and four backward on the
    selected pairs alone, and the indexer loss's one more pass of q . k over
    them; the indexer's scores forward and backward on the causal half. A
    kernel that walks every causal pair to reach the selected ones does more
    than this, and the count does not credit it."""
    per_expert = 3 * config.d_model * config.d_expert
    active = config.n_layer * (_matmul_params(config) + config.experts_per_token * config.held
                               / config.n_experts * per_expert) + config.vocab_size * config.d_model
    pairs = selected_pairs(seq_len, config.index_topk) / seq_len  # a token's, a head
    attention = 12.0 * config.n_head * config.head_dim * pairs
    if config.sparse:
        attention += 2.0 * config.n_head * config.head_dim * pairs
        attention += 6.0 * config.index_n_heads * config.index_head_dim * (seq_len + 1) / 2
    return 6.0 * active + config.n_layer * attention


# --------------------------------------------------------------------------- init
def init_params(config: KeyeVL2Config, key) -> Dict[str, Any]:
    """`gqa_experts.init_params` (which says why the embedding's rows are N(0, 1)) over this model's leaves."""
    return gqa_experts.init_params(config, key, _layer_shapes(config))


def param_logical_axes(config: KeyeVL2Config) -> Dict[str, Any]:
    return gqa_experts.param_logical_axes(config, _layer_shapes(config))


# --------------------------------------------------------------------------- forward
def rope_streams(positions, config: KeyeVL2Config):
    """(cos, sin, the indexer's cos, sin), each (S, B, pairs) with the sequence
    leading, as `stack.apply_stack` takes per-position streams. `positions`
    (3, B, S): a head's `head_dim / 2` frequencies in runs of `mrope_section`
    turn by the first, second, third component; the indexer's `index_head_dim /
    2` all by the first."""
    def tables(pairs: int, component):
        freqs = config.rope_theta ** (-jnp.arange(pairs, dtype=jnp.float32) / pairs)
        angles = positions.astype(jnp.float32)[component, :, :].transpose(2, 1, 0) * freqs  # (S, B, pairs)
        return jnp.cos(angles), jnp.sin(angles)

    component = np.repeat(np.arange(3), config.mrope_section)
    # positions[component] is (pairs, B, S): each frequency's own component.
    streams = tables(config.head_dim // 2, component)
    if config.sparse:
        streams += tables(config.index_head_dim // 2, np.zeros((config.index_head_dim // 2,), np.int32))
    return streams


def text_positions(tokens):
    """Text: the three components equal, 0 .. S - 1 in every row (one row stands for all)."""
    return jnp.broadcast_to(jnp.arange(tokens.shape[1]), (3, 1, tokens.shape[1]))


def layer_norm(x, scale, bias, eps):
    xf = x.astype(jnp.float32)
    mean = xf.mean(axis=-1, keepdims=True)
    var = jnp.mean((xf - mean) ** 2, axis=-1, keepdims=True)
    return (xf - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _parts(config: KeyeVL2Config, stats: bool = False):
    """(qkv_part, out_part, attend) of one layer (`stack.block`). x: (B, S, D);
    the streams are this rank's rows of `rope_streams`. `out_part` returns (x,
    aux): the layer's weighted auxiliary losses, or with `stats` what `moe_mlp`
    reports and what `attend` counted. `attend` is None for a dense
    configuration. The scope names are read from the compiled program's
    `op_name`s (PERF.md, "names")."""
    cdt, eps = config.dtype, config.norm_eps

    def qkv_part(x, layer, cos, sin, *index_tables):
        h = rms_norm(x, layer["attn_norm"], eps).astype(cdt)
        q, k, v = gqa_experts.qkv_heads(h, layer, by_batch(cos), by_batch(sin), config)
        if not config.sparse:
            return q, k, v
        with jax.named_scope("indexer"):
            ix, cos_i, sin_i = layer["indexer"], *map(by_batch, index_tables)
            h = jax.lax.stop_gradient(h)
            q_i = apply_rope(jnp.einsum("bsd,dnh->bnsh", h, ix["wq"].astype(cdt)), cos_i, sin_i)
            k_i = jnp.einsum("bsd,dh->bsh", h, ix["wk"].astype(cdt))
            k_i = layer_norm(k_i, ix["k_norm"], ix["k_norm_bias"], eps).astype(cdt)
            k_i = apply_rope(k_i[:, None], cos_i, sin_i)[:, 0]
            w = jnp.einsum("bsd,dn->bsn", h, ix["ww"].astype(cdt), preferred_element_type=jnp.float32)
            w = w * (config.index_n_heads * config.index_head_dim) ** -0.5
        return q, k, v, (q_i, k_i, w)

    def attend(q, k, v, more, attention_fn, mesh):  # `more`: qkv_part's fourth, (q_i, k_i, w)
        from ray_tpu.ops import lightning_indexer as li
        from ray_tpu.ops.flash_attention import flash_attention

        if attention_fn is not None:
            raise NotImplementedError("a selection of keys under an injected attention (ring, Ulysses)")
        if mesh is not None and mesh.size > 1:
            raise NotImplementedError("the selection's kernels over more than one device")
        backend = "xla" if config.attention == "xla" else None
        keep, lse_i = li.select(*more, config.index_topk, backend=backend, mesh=mesh)
        o, lse = flash_attention(q, k, v, causal=True, keep=keep, return_lse=True, mesh=mesh, backend=backend)
        loss = li.index_loss(q, k, lse, keep, *more, lse_i, backend=backend, mesh=mesh)
        if stats:
            return o, {**li.selection_counts(keep), "keep": keep, "index_loss": loss}
        return o, config.index_loss_weight * loss

    def out_part(x, o, layer, rng, further=None):
        del rng  # no dropout
        x, aux = gqa_experts.out_and_experts(x, o, layer, config)
        if stats:
            return x, {"moe": aux, "selection": further}
        aux = config.aux_loss_weight * aux["load_balance"]
        return x, aux if further is None else aux + further

    return qkv_part, out_part, attend if config.sparse else None


def forward(
    params: Dict[str, Any],
    tokens,  # (B, S) int32
    config: KeyeVL2Config,
    attention_fn: Optional[Callable] = None,
    step_rng=None,  # the step's key (`make_train_step`): nothing here draws
    mesh=None,
    num_microbatches: Optional[int] = None,
    return_aux: bool = False,
    positions=None,  # (3, B, S) int: the three rotary components; None is text
):
    """Logits (B, S, vocab) f32 against the head (untied); with `return_aux`,
    also the weighted sum over the layers of the indexer's loss and the
    load-balancing term."""
    del step_rng
    with jax.named_scope("embed"):
        x = params["embed"].astype(config.dtype)[tokens]
    qkv_part, out_part, attend = _parts(config)
    x, aux = apply_stack(
        params["blocks"], x, config, qkv_part, out_part, attention_fn=attention_fn, mesh=mesh,
        num_microbatches=num_microbatches, attend=attend,
        seq_streams=rope_streams(text_positions(tokens) if positions is None else positions, config),
    )
    logits = lm_head(
        x, lambda x: rms_norm(x, params["final_norm"], config.norm_eps), params["lm_head"], config.dtype)
    return (logits, aux) if return_aux else logits


# Mean next-token cross entropy plus the layers' auxiliary losses: `stack.lm_loss`'s arguments after `forward`.
loss_fn = functools.partial(lm_loss, forward)


def layer_stats(params, tokens, config: KeyeVL2Config):
    """`_parts(stats=True)`'s aux of every layer (leading axis) on `tokens` (B, S
    + 1), a batch's rows: what `routing_stats` and `selection_stats` read, for
    a caller that wants both from one walk."""
    inputs = tokens[:, :-1]
    x = params["embed"].astype(config.dtype)[inputs]
    qkv_part, out_part, attend = _parts(config, stats=True)
    streams = rope_streams(text_positions(inputs), config)

    def through(x, layer):
        return block(x, layer, config, qkv_part, out_part, streams=streams, attend=attend)

    return jax.lax.scan(through, x, params["blocks"])[1]


def routing_stats(params: Dict[str, Any], tokens, config: KeyeVL2Config, walked=None) -> Dict[str, Any]:
    """What the routers did with `tokens` (B, S + 1), per layer (leading axis):
    as `glm4_moe_lite.routing_stats` reports it, the load-balancing term beside.
    `walked`: `layer_stats` of the same arguments, where the caller has it."""
    aux = (walked or layer_stats(params, tokens, config))["moe"]
    return gqa_experts.routing_stats(aux, tokens[:, :-1].size * config.experts_per_token)


def selection_stats(params: Dict[str, Any], tokens, config: KeyeVL2Config, walked=None) -> Dict[str, Any]:
    """What the indexers selected on `tokens` (B, S + 1), per layer (leading
    axis): `selected_pairs`, `causal_pairs`, `keys_per_query_min` / `_max`,
    `live_tiles` and `tiles` (`lightning_indexer.selection_counts`: 512 x 512
    pairs of tiles on or under the diagonal, and those with a selected key),
    `index_loss`, each layer's own unweighted, and `keep` (L, B, S, words), the
    selections themselves in `flash_attention`'s packed form."""
    return (walked or layer_stats(params, tokens, config))["selection"]
