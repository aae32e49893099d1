"""Olmo-Hybrid for the benchmark: the system under test built through ray_tpu's
public API, a plain float32 reference written from the layer's equations, the
comparison that decides `correct`, and the arithmetic of FLOPs and bytes.

A configuration file (`benchmark/configs/<name>.json`) with `"model":
"olmo_hybrid"` is served by this module. Keys read, under the names of the
source's `config.json`: `layer_types`, `hidden_size`, `intermediate_size`,
`num_attention_heads`, `num_key_value_heads`, `linear_num_key_heads`,
`linear_num_value_heads`, `linear_key_head_dim`, `linear_value_head_dim`,
`linear_conv_kernel_dim`, `linear_allow_neg_eigval`, `vocab_size`,
`max_position_embeddings`, `rms_norm_eps`, `rope_parameters`,
`tie_word_embeddings`, `attention_bias`; and the benchmark's own: `dtype`,
`param_dtype`, `remat_policy`, `attention`, `learning_rate` (the peak),
`warmup_steps` and `total_steps`.
"""

from __future__ import annotations

import contextlib
import math
from typing import Any, Dict, Optional

from benchmark.models import gpt2

LINEAR, FULL = "linear_attention", "full_attention"
GDN_CHUNK = 128  # `ops/gated_delta_rule.py CHUNK`, which the kernels' scope declares (`chunk_128`); a test holds the two equal

# ------------------------------------------------------------------ arithmetic
# No jax below this line until `build`: the parent and the tests use these.


def _layers(c: Dict[str, Any]) -> Dict[str, int]:
    return {"linear": c["layer_types"].count(LINEAR), "full": c["layer_types"].count(FULL)}


def _linear_widths(c: Dict[str, Any]):
    heads = c["linear_num_value_heads"]
    return heads * c["linear_key_head_dim"], heads * c["linear_value_head_dim"]


def matmul_params(c: Dict[str, Any]) -> int:
    """Parameters a token meets as an operand of a matrix multiplication: a
    linear layer's q, k (hidden x 30 x 96 each), v, output gate and output
    (hidden x 30 x 192 each); a full layer's four projections; the SwiGLU's
    three matrices in every layer; the untied head. The embedding is a lookup;
    the gates' two vectors a head (`w_a`, `w_b`: hidden x 30 each), the
    convolutions' taps and the norms multiply nothing on the MXU's scale."""
    d, n = c["hidden_size"], _layers(c)
    keys, values = _linear_widths(c)
    return (n["linear"] * d * (2 * keys + 3 * values) + n["full"] * 4 * d * d
            + (n["linear"] + n["full"]) * 3 * d * c["intermediate_size"] + c["vocab_size"] * d)


def gdn_flops_per_token(c: Dict[str, Any], chunk: int = GDN_CHUNK, backward: bool = True) -> float:
    """FLOPs the gated delta rule asks for a token and head in the chunked form
    at `chunk` positions (2 a multiply-add): forward K K^T and Q K^T (2 C d_k
    each), K S, Q S and K^T N (2 d_k d_v each), T R and P N (2 C d_v each) and
    the triangular solve behind T (C^2); backward the forward's first four
    again (the kernel keeps the chunks' states, not T or N), six further
    products against the state's shape, four against C x d_v and four against
    C x d_k. The doubling that makes T in place of a substitution (2 log2 C
    products of C^3 a chunk) is the kernel's choice and is not counted."""
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    by_state, by_keys, by_values, solve = 2 * dk * dv, 2 * chunk * dk, 2 * chunk * dv, chunk * chunk
    forward = 2 * by_keys + 3 * by_state + 2 * by_values + solve
    if not backward:
        return float(forward)
    return float(forward + (2 + 4) * by_keys + (1 + 6) * by_state + (1 + 4) * by_values + solve)


def gdn_flops_per_step(c: Dict[str, Any], rows: int, seq: int, chunk: int = GDN_CHUNK) -> float:
    """`gdn_flops_per_token` over the device's rows, the heads and the linear layers."""
    return gdn_flops_per_token(c, chunk) * rows * seq * c["linear_num_value_heads"] * _layers(c)["linear"]


def gdn_bytes_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """Bytes the two kernels must move a step: forward reads q, k, v and writes
    o; backward reads q, k, v and o's gradient and writes the three gradients
    (the activations' type); the two gates a token and head in f32, read by
    both and their gradients written. The chunks' states that the forward
    kernel keeps for the backward one (d_k x d_v x 4 B a chunk: as many bytes
    again as the forward's operands at 64 positions a chunk) are the kernel's
    choice and are not counted."""
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    act = {"bfloat16": 2, "float32": 4}[c["dtype"]]
    per_token = act * ((2 * dk + 2 * dv) + (2 * dk + 2 * dv) + (2 * dk + dv)) + 4 * (2 + 2 + 2)
    return float(per_token) * rows * seq * c["linear_num_value_heads"] * _layers(c)["linear"]


def train_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    """FLOPs the forward and backward passes require per token: 6 per matmul
    parameter, attention over the full square of `seq` positions in the full
    layers (12 * hidden * seq each, `gpt2.train_flops_per_token`'s
    convention), and the scan's products in the linear layers. Recomputation
    is not counted."""
    return (6.0 * matmul_params(c) + 12.0 * _layers(c)["full"] * c["hidden_size"] * seq
            + gdn_flops_per_token(c) * c["linear_num_value_heads"] * _layers(c)["linear"])


def _attention_as_gpt2(c: Dict[str, Any]) -> Dict[str, Any]:
    return {"n_embd": c["hidden_size"], "n_head": c["num_attention_heads"], "n_layer": _layers(c)["full"]}


def flash_flops_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """`gpt2.flash_flops_per_step` at this configuration's heads, for its full layers alone."""
    return gpt2.flash_flops_per_step(_attention_as_gpt2(c), rows, seq)


def flash_bytes_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    return gpt2.flash_bytes_per_step(_attention_as_gpt2(c), rows, seq)


# ---------------------------------------------------------------------- system
def olmo_hybrid_config(c: Dict[str, Any]):
    import jax.numpy as jnp

    from ray_tpu.models.olmo_hybrid import OlmoHybridConfig

    assert c["attention_bias"] is False and c["tie_word_embeddings"] is False, "the only form written"
    assert c["rope_parameters"] == {"rope_theta": None}, "no rotary: the source's rope_theta is null"
    assert c["linear_num_key_heads"] == c["linear_num_value_heads"], "a key head a value head"
    assert c["num_key_value_heads"] == c["num_attention_heads"], "the full layers' heads are not grouped"
    return OlmoHybridConfig(
        vocab_size=c["vocab_size"], layer_types=tuple(c["layer_types"]), n_head=c["num_attention_heads"],
        d_model=c["hidden_size"], d_ff=c["intermediate_size"], linear_heads=c["linear_num_value_heads"],
        linear_key_dim=c["linear_key_head_dim"], linear_value_dim=c["linear_value_head_dim"],
        conv_kernel=c["linear_conv_kernel_dim"], allow_neg_eigval=c["linear_allow_neg_eigval"],
        max_seq_len=c["max_position_embeddings"], norm_eps=c["rms_norm_eps"],
        dtype=jnp.dtype(c["dtype"]), param_dtype=jnp.dtype(c["param_dtype"]),
        remat_policy=c["remat_policy"], attention=c["attention"],
    )


class System:
    """cfg, optimizer, state and jitted step, made as a user makes them."""

    def __init__(self, c: Dict[str, Any], mesh, seed: int):
        import jax

        from ray_tpu.models import create_train_state, default_optimizer, make_train_step

        self.c = c
        self.mesh = mesh
        self.cfg = olmo_hybrid_config(c)
        self.optimizer = default_optimizer(
            learning_rate=c["learning_rate"], warmup_steps=c.get("warmup_steps", 0),
            total_steps=c.get("total_steps", 0))
        self.state = create_train_state(self.cfg, jax.random.PRNGKey(seed), self.optimizer, mesh=mesh)
        self.step = make_train_step(self.cfg, self.optimizer, mesh=mesh)

    def param_shardings(self):
        """The parameters' shardings as they are laid out, or None on one device."""
        import jax

        if self.mesh is None or self.mesh.size == 1:
            return None
        return jax.tree.map(lambda p: p.sharding, self.state.params)

    def attention_path(self, rows_per_device: int, seq: int, platform: str) -> str:
        """"pallas" where the full layers run the flash kernels and the linear ones the scan's."""
        from ray_tpu.ops import gated_delta_rule as gdn
        from ray_tpu.ops.flash_attention import select_backend

        flash = select_backend((rows_per_device, self.cfg.n_head, seq, self.cfg.head_dim), platform)
        return flash if gdn.select_backend(platform) == "pallas" else "xla"


def build(c: Dict[str, Any], mesh, seed: int) -> System:
    return System(c, mesh, seed)


# ------------------------------------------------------------------- reference
RECURRENCE_BLOCK = 64  # positions the recurrence's backward pass makes again at a time


def rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def causal_conv(z, taps):
    """z (batch, seq, channels) against `taps` (kernel, channels): depthwise and
    causal, tap j on position t - (kernel - 1) + j, zeros before the row's
    first position, no bias. Written as the sum over the shifted copies."""
    import jax.numpy as jnp

    kernel, seq = taps.shape[0], z.shape[1]
    out = jnp.zeros_like(z)
    for j in range(kernel):
        back = kernel - 1 - j
        out = out + taps[j] * jnp.concatenate([jnp.zeros_like(z[:, :back]), z[:, :seq - back]], axis=1)
    return out


def delta_rule_recurrence(q, k, v, g, beta, block: int = RECURRENCE_BLOCK):
    """One head of one row, token by token: q, k (seq, d_k), v (seq, d_v), g and
    beta (seq,). `S' = exp(g_t) S`, `u = beta_t (v_t - S'^T k_t)`, `S = S' + k_t
    u^T`, `o_t = S^T q_t`, S zero before the first token. The positions run in
    blocks of `block`, each made again in the backward pass (`jax.checkpoint`):
    that pass holds a state a block and a block's states, not one a position.
    The arithmetic is the recurrence's, in the order written."""
    import jax
    import jax.numpy as jnp

    seq = q.shape[0]
    pad = -seq % block
    if pad:  # k = 0, beta = 0, g = 0: no write, no decay; the outputs there are cut off
        q, k, v = (jnp.concatenate([x, jnp.zeros((pad, x.shape[1]), x.dtype)]) for x in (q, k, v))
        g, beta = (jnp.concatenate([x, jnp.zeros((pad,), x.dtype)]) for x in (g, beta))

    def token(s, x):
        q_t, k_t, v_t, g_t, beta_t = x
        s = jnp.exp(g_t) * s
        u = beta_t * (v_t - s.T @ k_t)
        s = s + jnp.outer(k_t, u)
        return s, s.T @ q_t

    @jax.checkpoint
    def tokens_of_a_block(s, xs):
        return jax.lax.scan(token, s, xs)

    blocks = tuple(x.reshape(-1, block, *x.shape[1:]) for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(tokens_of_a_block, jnp.zeros((k.shape[1], v.shape[1]), q.dtype), blocks)
    return o.reshape(-1, v.shape[1])[:seq]


def layers_in_order(blocks, c: Dict[str, Any]):
    """(kind, the layer's own parameters) of every layer in the published
    order, out of the tree the system trains: one stack for every place in the
    period, the same place of every period on its leading axis."""
    import jax

    n_periods = jax.tree.leaves(blocks["period"])[0].shape[0]
    own = [jax.tree.map(lambda a, p=p: a[p], place) for p in range(n_periods) for place in blocks["period"]]
    assert len(own) == len(c["layer_types"]) and not blocks["leading"] and not blocks["trailing"]
    return list(zip(c["layer_types"], own))


HEAD_BLOCKS = 8  # the head's logits are made a block of positions at a time


def reference_loss(params, tokens, c: Dict[str, Any], dtype=None):
    """Olmo-Hybrid (the equations of ISSUE 51 and `models/olmo_hybrid.py`'s
    docstring; the source's `config.json` fixes the sizes, the Gated DeltaNet
    paper and OLMo 2's block the rest) in float32 `jax.numpy`; returns (loss,
    {"neg_eigval_share": the share of beta_t > 1, "decay_min": the least
    exp(g_t)} over the linear layers).

    Post-norm block, RMSNorm (eps `rms_norm_eps`), no bias: `h = x +
    N(mixer(x))`, `y = h + N(mlp(h))`, `mlp = W_d (silu(W_g h) * W_u h)`. A
    `linear_attention` mixer: q, k, v projections, each through a causal
    depthwise convolution of `linear_conv_kernel_dim` taps (`causal_conv`)
    and SiLU; q and k L2-normalised over a head's `linear_key_head_dim` (`x /
    sqrt(sum x^2 + 1e-6)`), q scaled by its -1/2 power; `beta = 2 sigmoid(w_b
    . x)` (`linear_allow_neg_eigval`), `g = -exp(A_log) softplus(w_a . x +
    dt_bias)`; the recurrence token by token (`delta_rule_recurrence`); the
    output RMS-normed over a head's `linear_value_head_dim` with one scale of
    that width, gated by `silu(W_z x)`, then `W_o`. A `full_attention` mixer:
    q and k RMS-normed over the whole projection, heads of hidden / heads,
    causal softmax at head_dim^-1/2, `W_o`, no rotary. Final RMSNorm, an
    untied head, mean cross entropy of the next token. No kernel, no chunked
    form, no bf16.

    Takes the parameter tree the system trains (`layers_in_order`; every
    matrix as (in, out), a convolution's taps as (kernel, channels)).
    Departures from a line-by-line transcription, none changes the arithmetic:
    each layer, each full layer's head and each block of the recurrence is
    made again in the backward pass (`jax.checkpoint`), so that neither 30
    heads' 4096 x 4096 scores nor 4,096 states of 96 x 192 a head are held at
    once beside the training state; the head's logits and their cross entropy
    are made `HEAD_BLOCKS` blocks of positions at a time (a row of 4,096
    against 100,352 words is 1.6 GB in float32).

    `dtype` (default float32) computes everything, parameters, state, decay
    and logits included, in that type instead: what a lower precision than the
    configuration states would give, for PERF.md's second reading.
    """
    import jax
    import jax.numpy as jnp

    f = jnp.dtype(dtype or jnp.float32)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    batch, seq = inputs.shape
    d, eps = c["hidden_size"], c["rms_norm_eps"]
    n_head = c["num_attention_heads"]
    head_dim = d // n_head
    lin_heads, dk, dv = c["linear_num_value_heads"], c["linear_key_head_dim"], c["linear_value_head_dim"]
    beta_scale = 2.0 if c["linear_allow_neg_eigval"] else 1.0
    mask = jnp.tril(jnp.ones((seq, seq), bool))

    @jax.checkpoint
    def one_head(q, k, v):  # (batch, seq, head_dim) each
        scores = jnp.einsum("bqh,bkh->bqk", q, k) / jnp.sqrt(jnp.asarray(head_dim, f))
        scores = jnp.where(mask, scores, -jnp.inf)
        return jnp.einsum("bqk,bkh->bqh", jax.nn.softmax(scores, axis=-1), v)

    def full_attention(x, layer):
        q = rms_norm(x @ layer["wq"], layer["q_norm"], eps)
        k = rms_norm(x @ layer["wk"], layer["k_norm"], eps)
        v = x @ layer["wv"]
        by_head = lambda z: z.reshape(batch, seq, n_head, head_dim).transpose(2, 0, 1, 3)  # noqa: E731
        out = jax.lax.map(lambda qkv: one_head(*qkv), (by_head(q), by_head(k), by_head(v)))
        return out.transpose(1, 2, 0, 3).reshape(batch, seq, d) @ layer["wo"], None

    def linear_attention(x, layer):
        def conv(w, taps, width):
            z = jax.nn.silu(causal_conv(x @ layer[w], layer[taps]))
            return z.reshape(batch, seq, lin_heads, width).transpose(0, 2, 1, 3)

        q, k, v = conv("wq", "conv_q", dk), conv("wk", "conv_k", dk), conv("wv", "conv_v", dv)
        unit = lambda z: z / jnp.sqrt((z * z).sum(-1, keepdims=True) + jnp.asarray(1e-6, f))  # noqa: E731
        q, k = unit(q) / jnp.sqrt(jnp.asarray(dk, f)), unit(k)
        beta = beta_scale * jax.nn.sigmoid(x @ layer["w_b"]).transpose(0, 2, 1)  # (batch, heads, seq)
        g = -jnp.exp(layer["A_log"])[None, :, None] * jax.nn.softplus(
            (x @ layer["w_a"]).transpose(0, 2, 1) + layer["dt_bias"][None, :, None])
        o = jax.vmap(jax.vmap(delta_rule_recurrence))(q, k, v, g, beta)  # (batch, heads, seq, dv)
        o = rms_norm(o, layer["o_norm"], eps).transpose(0, 2, 1, 3).reshape(batch, seq, lin_heads * dv)
        stats = ((beta > 1).mean(), jnp.exp(g).min())
        return (o * jax.nn.silu(x @ layer["wz"])) @ layer["wo"], stats

    def block(kind):
        @jax.checkpoint
        def apply(x, layer):
            layer = jax.tree.map(lambda p: p.astype(f), layer)
            mixed, stats = (linear_attention if kind == LINEAR else full_attention)(x, layer)
            h = x + rms_norm(mixed, layer["mixer_norm"], eps)
            y = (jax.nn.silu(h @ layer["w_gate"]) * (h @ layer["w_up"])) @ layer["w_down"]
            return h + rms_norm(y, layer["mlp_norm"], eps), stats
        return apply

    @jax.checkpoint
    def head_block(head, xs):
        x, t = xs  # (batch, positions, d), (batch, positions)
        logp = jax.nn.log_softmax(x @ head.T, axis=-1)
        return -jnp.take_along_axis(logp, t[..., None], axis=-1).sum()

    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(f)[inputs]
        share, least = [], []
        for kind, layer in layers_in_order(params["blocks"], c):
            x, stats = block(kind)(x, layer)
            if stats is not None:
                share.append(stats[0])
                least.append(stats[1])
        x = rms_norm(x, params["final_norm"].astype(f), eps)
        n = HEAD_BLOCKS if seq % HEAD_BLOCKS == 0 else 1
        by_block = lambda z: jnp.moveaxis(z.reshape(batch, n, seq // n, *z.shape[2:]), 1, 0)  # noqa: E731
        head = params["head"].astype(f)
        total = jax.lax.map(lambda xs: head_block(head, xs), (by_block(x), by_block(targets))).sum()
        loss = (total / (batch * seq)).astype(jnp.float32)
        return loss, {"neg_eigval_share": jnp.stack(share).mean().astype(jnp.float32),
                      "decay_min": jnp.stack(least).min().astype(jnp.float32)}


# Tolerances of the agreement between the system (bf16 activations and matmul
# operands, the flash kernels and the scan's kernels in chunks of 128 with an
# f32 state; f32 gates, norms, logits and parameters) and the reference (f32
# throughout, the recurrence token by token), at seeded initial weights, on the
# four rows (16,384 tokens, a row a chip) of the run's first batch that the
# harness hands `check`: the timed shape. Measured on the four chips at the
# published widths (PR 51, PERF.md section 6; six readings of the system, each
# its own seed; one of the reference itself with parameters, state, decay,
# norms and logits in bf16, the nearest precision below the configuration's;
# one of the system with the state a chunk starts from rounded to bf16):
#   loss           system off by 5.9e-5..1.93e-3 (a loss of 12.3 over 100,352
#                  words); the bf16 reference by 3.84e-2: the limit that tells
#                  the precision, three times the system's largest reading and
#                  a sixth of the bf16 reference's
#   gradient norm  system 4.6e-4..2.3e-3; the bf16 reference 1.9e-3: it cannot
#                  tell the precision. Four times the largest reading, for
#                  another function (a norm moved, a gate's sign)
#   wk, w_a        the first linear layer's, which only the scan's backward pass
#                  reaches (dk; dg): system 1.6e-5..2.3e-3; the bf16 reference
#                  2.5e-3 and 5.4e-3. Nine times the largest reading, for
#                  another function: with the decay left out `w_a` reads 1.0
#   A_log          30 numbers, each a sum of 16,384 signed terms g dg: system
#                  5.0e-3..5.9e-2, the bf16 reference 0.12: the limit is four
#                  times the largest reading and is there for the decay left
#                  out (1.0), nothing finer
# What none of them tells at these widths is the state alone in bf16 (rounded
# where a chunk hands it on: loss 1.0e-3, gradient norm 2.8e-3, wk 2.7e-3,
# A_log 5.0e-2, inside the system's own range or at its edge): 2^-9 of a state
# is what every bf16 activation round it already carries. That fault is held
# where it can be told, in float32 on the CPU (`tests/test_gated_delta_rule.py`,
# `tests/test_olmo_hybrid.py`: 60 times those tests' limit), and PERF.md
# section 7 has the row. No comparison of losses can see parameters kept in
# bf16: the parameters' and the optimizer moments' dtype is checked by name.
# PR 56, second session (PERF.md sections 6 and 7, first row): sixteen more seeds of the cell's own runs after PR 52
# and PR 54 read loss 5.7e-6..4.43e-3, gradient norm 1.2e-5..2.8e-3, wk 1.4e-4..2.6e-3, w_a 4.3e-4..3.63e-3, A_log
# 1.9e-3..0.147, every one inside the limits, which stand as PR 51 set them. Seed 797891267 reads gradient norm 0.1123
# and wk 0.0587 (loss 3.0e-3, w_a 2.5e-3, A_log 2.0e-2), the same in three runs and in the timed step's own first
# gradient norm (`first_step`, which `worker.warmup` puts beside these): forty times every other seed's, by its tokens
# and not by its parameters. That is no reading of a sound run and sets no limit: no limit is moved for it.
LOSS_ABS_TOL = 6e-3
GRAD_NORM_REL_TOL = 1e-2
LEAF_GRAD_NORM_REL_TOL = {"wk": 2e-2, "w_a": 2e-2, "A_log": 0.25}
CHECKED_LEAVES = ("wk", "w_a", "A_log")  # of the first linear layer


def _leaf_norms(grads):
    """The gradient's norm at each of `CHECKED_LEAVES` of the first period's first (linear) layer."""
    import jax.numpy as jnp

    first = grads["blocks"]["period"][0]
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(first[name][0].astype(jnp.float32)))) for name in CHECKED_LEAVES])


def losses_and_norms(system: System, dtype=None):
    """(of_system, of_reference): each `(params, tokens) -> (loss, the
    gradient's global norm, its norm at the checked leaves[, the reference's
    statistics])`, a program each so that the two gradient trees (2.4 GB a
    chip each at the published widths) are never held at once."""
    import jax
    import optax

    from ray_tpu.models import olmo_hybrid

    cfg, mesh, c = system.cfg, system.mesh, system.c
    shardings = system.param_shardings()

    def norms(grads):
        # Laid out like the parameters: left alone, XLA may all-reduce whole float32 gradients onto every chip.
        if shardings is not None:
            grads = jax.tree.map(jax.lax.with_sharding_constraint, grads, shardings)
        return optax.global_norm(grads), _leaf_norms(grads)

    def of_system(params, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: olmo_hybrid.loss_fn(p, {"tokens": tokens}, cfg, mesh=mesh))(params)
        return (loss, *norms(grads))

    def of_reference(params, tokens):
        (loss, stats), grads = jax.value_and_grad(
            lambda p: reference_loss(p, tokens, c, dtype), has_aux=True)(params)
        return (loss, *norms(grads), stats)

    return of_system, of_reference


@contextlib.contextmanager
def _moments_set_aside(system: System):
    """Before the first step AdamW's moments are zeros: they are dropped for the
    length of the block and made again, laid out as they were. At the published
    widths they are 4.87 GB a chip, and the check's two programs (XLA's peaks
    14.66 and 12.70 GB a chip with the parameters: ahead-of-time compiles, PR
    51) do not fit beside them; the step, which updates them, does. A state
    that has taken a step keeps its moments, and the check their room."""
    import jax

    state = system.state
    if int(state.step) != 0 or not jax.tree.leaves(state.opt_state):
        yield
        return
    shardings = jax.tree.map(lambda x: x.sharding, state.opt_state)
    state.opt_state = None
    try:
        yield
    finally:
        state.opt_state = jax.jit(system.optimizer.init, out_shardings=shardings)(state.params)


def check(system: System, tokens, *, loss_tol: Optional[float] = None, grad_tol: Optional[float] = None,
          leaf_tol=None, program=None, reference=None) -> Dict[str, Any]:
    """Loss, global gradient norm and the gradient's norm at three leaves of
    the first linear layer, of the system's `loss_fn` (through the flash
    kernels and the scan's) against the reference's, on `tokens` (a jax array,
    already placed under the system's mesh) with the run's own parameters.
    Nothing is gathered to the host but scalars. A limit that is not given is
    the configuration's own (`check_tolerances`: the rehearsal's toy, whose
    sums run over 64 terms), else this file's. `program`, `(params, tokens) ->
    (loss, the gradient's norm, its norm at the checked leaves)`, stands in
    the system's place (`tools/olmo_hybrid_readings.py`: the reference a
    precision below, the system under a planted fault), and `reference` is
    what the reference's program gave for these tokens where the caller has
    run it already."""
    import jax
    import jax.numpy as jnp

    own = system.c.get("check_tolerances", {})
    loss_tol = own.get("loss_abs", LOSS_ABS_TOL) if loss_tol is None else loss_tol
    grad_tol = own.get("grad_norm_rel", GRAD_NORM_REL_TOL) if grad_tol is None else grad_tol
    leaf_tol = own.get("leaf_grad_norm_rel", LEAF_GRAD_NORM_REL_TOL) if leaf_tol is None else leaf_tol
    if not isinstance(leaf_tol, dict):
        leaf_tol = dict.fromkeys(CHECKED_LEAVES, leaf_tol)  # one limit for the three leaves
    params = system.state.params
    want_dtype = jnp.dtype(system.c["param_dtype"])
    leaves = jax.tree.leaves(params) + [
        x for x in jax.tree.leaves(system.state.opt_state) if getattr(x, "ndim", 0) > 0]
    wrong_dtype = sorted({str(x.dtype) for x in leaves if x.dtype != want_dtype})
    del leaves
    of_system, of_reference = losses_and_norms(system)
    with _moments_set_aside(system):
        sys_loss, sys_norm, sys_leaves = jax.device_get(jax.jit(program or of_system)(params, tokens))
        if reference is None:
            reference = jax.device_get(jax.jit(of_reference)(params, tokens))
    ref_loss, ref_norm, ref_leaves, stats = reference
    got = [float(x) for x in (sys_loss, sys_norm, ref_loss, ref_norm, *sys_leaves, *ref_leaves)]
    rel = lambda a, b: abs(float(a) - float(b)) / max(float(b), 1e-30)  # noqa: E731
    out = {
        "loss_system": float(sys_loss), "loss_reference": float(ref_loss),
        "grad_norm_system": float(sys_norm), "grad_norm_reference": float(ref_norm),
        "loss_abs_err": abs(float(sys_loss) - float(ref_loss)),
        "grad_norm_rel_err": rel(sys_norm, ref_norm),
        "leaf_grad_norm_rel_err": {name: rel(a, b) for name, a, b in zip(CHECKED_LEAVES, sys_leaves, ref_leaves)},
        "leaf_grad_norm_reference": {name: float(b) for name, b in zip(CHECKED_LEAVES, ref_leaves)},
        "gdn.neg_eigval_share": float(stats["neg_eigval_share"]),
        "gdn.decay_min": float(stats["decay_min"]),
        "state_dtypes_other_than_stated": wrong_dtype,
        "limits": {"loss_abs_err": loss_tol, "grad_norm_rel_err": grad_tol, "leaf_grad_norm_rel_err": leaf_tol},
    }
    out["ok"] = bool(
        all(map(math.isfinite, got)) and out["loss_abs_err"] <= loss_tol
        and out["grad_norm_rel_err"] <= grad_tol and not wrong_dtype
        and all(err <= leaf_tol[name] for name, err in out["leaf_grad_norm_rel_err"].items()))
    return out
