"""SDAR (JetLM, `model_type` `sdar_moe`; the published sizes are
SDAR-30B-A3B-Chat's): the 30B-A3B family's layer, trained by block diffusion
(arXiv:2510.06303, after BD3-LMs).

    layer:     h = x + W_o Attn(q, k, v), q, k, v from N(x) (N = RMSNorm), 32 query heads on 4
               key/value heads, a norm over each head's own dimensions on q and on k, rotary
               over all of a head;  y = h + sum_{e in top 8} w_e SwiGLU_e(N(h)), w the 8 largest of
               softmax(N(h) W_r), renormalised; no shared expert, no dense layer
    noise:     a row x of L tokens in blocks of `block_length`; per row and block t_b ~
               U(`noise_eps`, 1); each token of the block becomes `mask_token_id` with
               probability t_b: x~
    objective: (1 / L) sum_b (1 / t_b) sum_{i in b, x~_i = mask} -log p(x_i | x~^b, x^{<b})
               + `aux_loss_weight` x load balance

One pass serves every block: the stack runs on the 2 L positions `[x ; x~]`,
both copies at rotary positions 0 .. L - 1, under `flash_attention.BlockDiffusion`
(a clean query sees the clean blocks up to its own; a noised query the clean
blocks before its own and its own noised block in both directions). The head
runs on the noised half alone and reads each position's own token: no shift.
Everything per position (norms, projections, router, experts) sees 2 L rows.

The layer is `gqa_experts.py`'s, shared with `keye_vl2.py`; the skeleton
`stack.py`'s, with `attend` where the attention dispatch stands, for the mask.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from ray_tpu.models import gqa_experts
from ray_tpu.models.gqa_experts import by_batch
from ray_tpu.models.llama import rms_norm, rope_tables
from ray_tpu.models.stack import apply_stack, block, causal_lm_loss, cross_entropy, lm_head


@dataclasses.dataclass(frozen=True)
class SdarConfig:
    """Defaults are SDAR-30B-A3B-Chat's published sizes (the source's key where the name differs)."""

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
    rope_theta: float = 1e6
    max_seq_len: int = 32768
    norm_eps: float = 1e-6
    aux_loss_weight: float = 0.001
    block_length: int = 4  # the released chat model's; config.json carries none
    mask_token_id: int = 151669  # <|MASK|> in the released tokenizer; a cut vocabulary names its own
    noise_eps: float = 1e-3  # t_b ~ U(noise_eps, 1)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    remat_policy: Optional[str] = "save_attn"  # as LlamaConfig's
    attention: str = "auto"  # auto | flash | xla

    def __post_init__(self):
        assert self.n_head % self.n_kv_head == 0
        assert 0 <= self.mask_token_id < self.vocab_size, "the mask token is an id of the vocabulary held here"

    @property
    def held(self) -> int:
        return self.n_experts if self.n_experts_held is None else self.n_experts_held

    @classmethod
    def nano(cls, **kw):
        """Tiny config for CPU tests: 8 experts of which this share holds 4, 2 a
        token; 4 query heads on 2 key/value heads of 16; blocks of 4."""
        kw.setdefault("vocab_size", 256)
        kw.setdefault("mask_token_id", 254)
        kw.setdefault("max_seq_len", 64)
        kw.setdefault("n_experts_held", 4)
        kw.setdefault("first_expert_held", 2)
        kw.setdefault("n_layer", 2)
        return cls(n_head=4, n_kv_head=2, head_dim=16, d_model=64, d_expert=32, n_experts=8,
                   experts_per_token=2, **kw)


# --------------------------------------------------------------------------- sizes
def num_params(config: SdarConfig) -> int:
    """Of this share: the experts held, not all the router names; embedding and head untied."""
    d = config.d_model
    return 2 * config.vocab_size * d + d + config.n_layer * gqa_experts.layer_params(config)


def kept_pairs(seq_len: int, block: int) -> int:
    """(query, key) pairs of one head that the mask keeps on a row of `seq_len`
    data tokens (2 x `seq_len` positions): with n = seq_len / block blocks, the
    clean copy's block-causal half n (n + 1) / 2, the noised copy's strict half
    n (n - 1) / 2 and its n diagonal blocks, n^2 + n blocks of block^2 pairs."""
    n = seq_len // block
    return (n * n + n) * block * block


def train_flops_per_token(config: SdarConfig, seq_len: int) -> float:
    """The model's FLOPs per data token: both copies meet every layer's matmul
    parameters (of their `experts_per_token` experts the share `held /
    n_experts`, in expectation), the noised copy alone the head; attention's two
    products forward and four backward on the kept pairs. 6 a parameter met."""
    per_expert = 3 * config.d_model * config.d_expert
    per_position = gqa_experts.matmul_params(config) + config.experts_per_token * config.held / config.n_experts * per_expert
    active = 2 * config.n_layer * per_position + config.vocab_size * config.d_model
    attention = 12.0 * config.n_head * config.head_dim * kept_pairs(seq_len, config.block_length) / seq_len
    return 6.0 * active + config.n_layer * attention


# --------------------------------------------------------------------------- init
def init_params(config: SdarConfig, key) -> Dict[str, Any]:
    """`gqa_experts.init_params`: normal 0.02, the output projections 0.02 / sqrt(2 x layers), the embedding's
    rows N(0, 1)."""
    return gqa_experts.init_params(config, key, gqa_experts.layer_shapes(config))


def param_logical_axes(config: SdarConfig) -> Dict[str, Any]:
    return gqa_experts.param_logical_axes(config, gqa_experts.layer_shapes(config))


# --------------------------------------------------------------------------- the objective's draw
def noise(tokens, key, config: SdarConfig):
    """Block diffusion's draw on `tokens` (B, L) int: per row and block `t_b ~
    U(noise_eps, 1)`, each token masked with probability `t_b`. -> (the noised
    ids (B, L), `masked` (B, L) bool, the loss's `weight` (B, L) f32: 1 / t_b
    where masked, 0 elsewhere). A function of its own so that whoever checks
    the program against a reference hands both one draw."""
    rows, seq = tokens.shape
    assert seq % config.block_length == 0, f"a row of {seq} in blocks of {config.block_length}"
    with jax.named_scope("noise"):
        key_t, key_mask = jax.random.split(key)
        t = jax.random.uniform(key_t, (rows, seq // config.block_length), jnp.float32, config.noise_eps, 1.0)
        t = jnp.repeat(t, config.block_length, axis=1)
        masked = jax.random.uniform(key_mask, (rows, seq), jnp.float32) < t
        return jnp.where(masked, config.mask_token_id, tokens), masked, jnp.where(masked, 1.0 / t, 0.0)


# --------------------------------------------------------------------------- forward
def _parts(config: SdarConfig, seq_len: int, stats: bool = False):
    """(qkv_part, out_part, attend) of one layer (`stack.block`) on x (B, 2 x
    `seq_len`, D). `out_part` returns (x, aux): the layer's weighted
    load-balance term, or with `stats` what `moe_mlp` reports."""
    from ray_tpu.ops.flash_attention import BlockDiffusion, flash_attention

    mask = BlockDiffusion(seq_len, config.block_length)

    def qkv_part(x, layer, cos, sin):
        h = rms_norm(x, layer["attn_norm"], config.norm_eps).astype(config.dtype)
        return gqa_experts.qkv_heads(h, layer, by_batch(cos), by_batch(sin), config)

    def attend(q, k, v, attention_fn, mesh):
        if attention_fn is not None:
            raise NotImplementedError("block diffusion's mask under an injected attention (ring, Ulysses)")
        if mesh is not None and int(mesh.shape.get("pipeline", 1)) > 1:
            mesh = None  # as `stack.resolve_attention`: the pipeline's manual region cannot be reopened
        backend = "xla" if config.attention == "xla" else None
        return (flash_attention(q, k, v, causal=mask, mesh=mesh, backend=backend),)

    def out_part(x, o, layer, rng):
        del rng  # no dropout
        x, aux = gqa_experts.out_and_experts(x, o, layer, config)
        return x, aux if stats else config.aux_loss_weight * aux["load_balance"]

    return qkv_part, out_part, attend


def _two_copies(params, tokens, noised, config: SdarConfig):
    """The stack's input (B, 2 L, D), the clean copy first, and its rotary
    streams (2 L, 1, pairs): both copies at positions 0 .. L - 1."""
    with jax.named_scope("noise"):
        ids = jnp.concatenate([tokens, noised], axis=1)
    with jax.named_scope("embed"):
        x = params["embed"].astype(config.dtype)[ids]
    cos, sin = rope_tables(tokens.shape[1], config.head_dim, config.rope_theta)
    return x, tuple(jnp.concatenate([table, table], axis=0)[:, None] for table in (cos, sin))


def forward(
    params: Dict[str, Any],
    tokens,  # (B, L) int32: the clean row
    noised,  # (B, L) int32: `noise`'s first
    config: SdarConfig,
    attention_fn: Optional[Callable] = None,
    mesh=None,
    num_microbatches: Optional[int] = None,
    return_aux: bool = False,
):
    """Logits (B, L, vocab) f32 at the noised copy's positions, each of its own
    token, against the head (untied); with `return_aux`, also the weighted sum
    over the layers of the load-balancing term."""
    seq_len = tokens.shape[1]
    x, streams = _two_copies(params, tokens, noised, config)
    qkv_part, out_part, attend = _parts(config, seq_len)
    x, aux = apply_stack(
        params["blocks"], x, config, qkv_part, out_part, attention_fn=attention_fn, mesh=mesh,
        num_microbatches=num_microbatches, attend=attend, seq_streams=streams)
    logits = lm_head(
        x[:, seq_len:], lambda x: rms_norm(x, params["final_norm"], config.norm_eps), params["lm_head"], config.dtype)
    return (logits, aux) if return_aux else logits


def _row(batch):
    """The data row of a batch: {"tokens": (B, L + 1)} as every loop hands it (the last id is the
    next-token objective's and is not read), or {"inputs": (B, L)}."""
    return batch["inputs"] if "inputs" in batch else batch["tokens"][:, :-1]


def loss_and_parts(params, batch, config: SdarConfig, attention_fn=None, step_rng=None, mesh=None,
                   num_microbatches=None):
    """(`loss_fn`'s loss, {`ce` (B, L): each noised position's cross entropy
    against its own token, masked or not; `masked` (B, L)}): the logits the
    loss read, position by position, for whoever compares precisions (the
    objective's weights reach 1 / `noise_eps`, and the masked positions, which
    carry one embedding, round alike: a mean hides what a position shows)."""
    tokens = _row(batch)
    noised, masked, weight = noise(tokens, jax.random.PRNGKey(0) if step_rng is None else step_rng, config)
    logits, aux = forward(params, tokens, noised, config, attention_fn, mesh, num_microbatches, return_aux=True)
    loss = causal_lm_loss(logits, tokens, weights=weight) + aux
    with jax.named_scope("loss"):
        ce = cross_entropy(logits, tokens)
    return loss, {"ce": jax.lax.stop_gradient(ce), "masked": masked}


def loss_fn(params, batch, config: SdarConfig, attention_fn=None, step_rng=None, mesh=None, num_microbatches=None):
    """Block diffusion's objective on `batch` under the draw `noise(row,
    step_rng, config)`: the cross entropy at the masked positions at weight 1 /
    t_b, over L, plus the layers' load-balance terms. `step_rng` None (an
    evaluation) is one fixed draw."""
    return loss_and_parts(params, batch, config, attention_fn, step_rng, mesh, num_microbatches)[0]


def routing_stats(params: Dict[str, Any], tokens, noised, config: SdarConfig) -> Dict[str, Any]:
    """What the routers did with the 2 L positions of `tokens` and `noised`
    (B, L each), per layer (leading axis): `gqa_experts.routing_stats`."""
    x, streams = _two_copies(params, tokens, noised, config)
    qkv_part, out_part, attend = _parts(config, tokens.shape[1], stats=True)

    def through(x, layer):
        return block(x, layer, config, qkv_part, out_part, streams=streams, attend=attend)

    aux = jax.lax.scan(through, x, params["blocks"])[1]
    return gqa_experts.routing_stats(aux, 2 * tokens.size * config.experts_per_token)
