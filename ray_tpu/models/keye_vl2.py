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
`flash_attention(keep=)` with its key/value heads unrepeated, and the expert
layer is `moe.moe_mlp`, told which experts this chip holds (`n_experts_held`).
`index_topk` None is the model without `sa_config`: dense grouped-query
attention, no indexer.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.llama import apply_rope, rms_norm
from ray_tpu.models.moe import moe_mlp
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
    """{name: (shape, init: a normal's std, or "ones" / "zeros", logical axes)} of one layer."""
    d, nh, nkv, hd, f = config.d_model, config.n_head, config.n_kv_head, config.head_dim, config.d_expert
    std, out_std = 0.02, 0.02 / math.sqrt(2 * config.n_layer)
    shapes: Dict[str, Any] = {
        "attn_norm": ((d,), "ones", (None,)), "mlp_norm": ((d,), "ones", (None,)),
        "wq": ((d, nh, hd), std, ("embed", "heads", None)),
        "wk": ((d, nkv, hd), std, ("embed", "kv_heads", None)),
        "wv": ((d, nkv, hd), std, ("embed", "kv_heads", None)),
        "q_norm": ((hd,), "ones", (None,)), "k_norm": ((hd,), "ones", (None,)),
        "wo": ((nh, hd, d), out_std, ("heads", None, "embed")),
        "moe": {
            "router_w": ((d, config.n_experts), std, ("embed", None)),
            "w_gate": ((config.held, d, f), std, ("expert", "embed", "mlp")),
            "w_up": ((config.held, d, f), std, ("expert", "embed", "mlp")),
            "w_down": ((config.held, f, d), out_std, ("expert", "mlp", "embed")),
        },
    }
    if config.sparse:
        hi, di = config.index_n_heads, config.index_head_dim
        shapes["indexer"] = {
            "wq": ((d, hi, di), std, ("embed", None, None)),
            "wk": ((d, di), std, ("embed", None)),
            "k_norm": ((di,), "ones", (None,)), "k_norm_bias": ((di,), "zeros", (None,)),
            "ww": ((d, hi), std, ("embed", None)),
        }
    return shapes


_is_shape = lambda x: isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)  # noqa: E731


def _matmul_params(config: KeyeVL2Config) -> int:
    """One layer's parameters that every token meets as an operand of a product."""
    d, hd = config.d_model, config.head_dim
    n = 2 * d * config.n_head * hd + 2 * d * config.n_kv_head * hd + d * config.n_experts
    if config.sparse:
        n += d * config.index_n_heads * (config.index_head_dim + 1) + d * config.index_head_dim
    return n


def num_params(config: KeyeVL2Config) -> int:
    """Of this share: the experts held, not all the router names; embedding and head untied."""
    d = config.d_model
    per_layer = (_matmul_params(config) + 3 * config.held * d * config.d_expert + 2 * d + 2 * config.head_dim
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
def _tree(config: KeyeVL2Config, layer_leaf: Callable, leaf: Callable):
    """A tree like the parameters': `layer_leaf(shape, init, axes)` for a layer's
    leaves (stacked over the layers), `leaf(shape, init, axes)` for the others.

    The embedding's rows are N(0, 1), `torch.nn.Embedding`'s own: at 0.02 a
    token's row (norm 0.9) is outweighed after one layer by the running mean of
    the values, which a group of 8 query heads on one key/value head adds up
    coherently (2.2) and which is the same vector for every query; the routers
    of the later layers then see one input, a row's 16,384 tokens all choose
    the same 8 experts, and a layer's held load is 0, 1, 2 or 3 times the
    even share by the draw (PERF.md section 6, PR 42)."""
    d = config.d_model
    return {
        "embed": leaf((config.vocab_size, d), 1.0, ("vocab", "embed")),
        "blocks": jax.tree.map(lambda spec: layer_leaf(*spec), _layer_shapes(config), is_leaf=_is_shape),
        "final_norm": leaf((d,), "ones", (None,)),
        "lm_head": leaf((config.vocab_size, d), 0.02, ("vocab", "embed")),
    }


def init_params(config: KeyeVL2Config, key) -> Dict[str, Any]:
    pd, counter = config.param_dtype, iter(range(1 << 30))

    def array(stack):
        def make(shape, init, axes):
            if isinstance(init, str):
                return jnp.full(stack + shape, {"ones": 1.0, "zeros": 0.0}[init], pd)
            return (jax.random.normal(jax.random.fold_in(key, next(counter)), stack + shape) * init).astype(pd)
        return make

    return _tree(config, array((config.n_layer,)), array(()))


def param_logical_axes(config: KeyeVL2Config) -> Dict[str, Any]:
    return _tree(config, lambda shape, init, axes: ("layers",) + axes, lambda shape, init, axes: axes)


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
    by_batch = lambda table: table.transpose(1, 0, 2)[:, None]  # (S, B, pairs) -> (B, 1, S, pairs)

    def qkv_part(x, layer, cos, sin, *index_tables):
        h = rms_norm(x, layer["attn_norm"], eps).astype(cdt)
        cos, sin = by_batch(cos), by_batch(sin)
        q = jnp.einsum("bsd,dnh->bnsh", h, layer["wq"].astype(cdt))
        k = jnp.einsum("bsd,dnh->bnsh", h, layer["wk"].astype(cdt))
        v = jnp.einsum("bsd,dnh->bnsh", h, layer["wv"].astype(cdt))
        q = apply_rope(rms_norm(q, layer["q_norm"], eps).astype(cdt), cos, sin)
        k = apply_rope(rms_norm(k, layer["k_norm"], eps).astype(cdt), cos, sin)
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
        with jax.named_scope("attn_out"):
            x = x + jnp.einsum("bnsh,nhd->bsd", o.astype(cdt), layer["wo"].astype(cdt))
        with jax.named_scope("moe"):
            h = rms_norm(x, layer["mlp_norm"], eps).astype(cdt)
            moe = layer["moe"]
            h, aux = moe_mlp(
                h, moe["router_w"], moe["w_gate"], moe["w_up"], moe["w_down"],
                k=config.experts_per_token, norm_topk_prob=config.norm_topk_prob,
                held_from=config.first_expert_held)
        if stats:
            return x + h, {"moe": aux, "selection": further}
        aux = config.aux_loss_weight * aux["load_balance"]
        return x + h, aux if further is None else aux + further

    return qkv_part, out_part, attend if config.sparse else None


def forward(
    params: Dict[str, Any],
    tokens,  # (B, S) int32
    config: KeyeVL2Config,
    attention_fn: Optional[Callable] = None,
    dropout_rng=None,  # accepted for API parity; no dropout
    mesh=None,
    num_microbatches: Optional[int] = None,
    return_aux: bool = False,
    positions=None,  # (3, B, S) int: the three rotary components; None is text
):
    """Logits (B, S, vocab) f32 against the head (untied); with `return_aux`,
    also the weighted sum over the layers of the indexer's loss and the
    load-balancing term."""
    del dropout_rng
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
    pairs = tokens[:, :-1].size * config.experts_per_token
    counts = aux["tokens_per_expert"]
    return {
        "experts": aux["experts"],
        "tokens_per_expert": counts,
        "load_max_over_mean": counts.max(axis=-1) / counts.mean(axis=-1),
        "load_balance": aux["load_balance"],
        "held_pairs": aux["held_pairs"],
        "elsewhere_pairs": pairs - aux["held_pairs"],
        "dropped": aux["held_pairs"] - aux["rows_processed"],
        "compact": aux["compact"],
    }


def selection_stats(params: Dict[str, Any], tokens, config: KeyeVL2Config, walked=None) -> Dict[str, Any]:
    """What the indexers selected on `tokens` (B, S + 1), per layer (leading
    axis): `selected_pairs`, `causal_pairs`, `keys_per_query_min` / `_max`,
    `live_tiles` and `tiles` (`lightning_indexer.selection_counts`: 512 x 512
    pairs of tiles on or under the diagonal, and those with a selected key),
    `index_loss`, each layer's own unweighted, and `keep` (L, B, S, words), the
    selections themselves in `flash_attention`'s packed form."""
    return (walked or layer_stats(params, tokens, config))["selection"]
