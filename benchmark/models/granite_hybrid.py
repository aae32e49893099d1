"""Granite 4.0-H (IBM, `model_type` `granitemoehybrid` with no experts) for the benchmark: the system under test built
through ray_tpu's public API, a plain float32 reference written from the layers' equations, the comparison that decides
`correct`, and the arithmetic of FLOPs and bytes.

A configuration file (`benchmark/configs/<name>.json`) with `"model": "granite_hybrid"` is served by this module. Keys
read, under the names of the source's `config.json`: `layer_types`, `hidden_size`, `shared_intermediate_size`,
`num_attention_heads`, `num_key_value_heads`, `mamba_n_heads`, `mamba_d_head`, `mamba_d_state`, `mamba_n_groups`,
`mamba_d_conv`, `mamba_expand`, `mamba_conv_bias`, `mamba_proj_bias`, `embedding_multiplier`, `attention_multiplier`,
`residual_multiplier`, `logits_scaling`, `vocab_size`, `max_position_embeddings`, `rms_norm_eps`,
`position_embedding_type`, `tie_word_embeddings`, `attention_bias`, `num_local_experts`; and the benchmark's own:
`ssd_chunk`, `dtype`, `param_dtype`, `remat_policy`, `attention`, `learning_rate` (the peak), `warmup_steps` and
`total_steps`.
"""

from __future__ import annotations

import contextlib
import math
from typing import Any, Dict, Optional

MAMBA, ATTENTION = "mamba", "attention"

# ------------------------------------------------------------------ arithmetic
# No jax below this line until `build`: the parent and the tests use these.
# Everything counts what this chip computes: the layers and the slice of the vocabulary it holds.


def _layers(c: Dict[str, Any]) -> Dict[str, int]:
    return {kind: c["layer_types"].count(kind) for kind in (MAMBA, ATTENTION)}


def _mamba_widths(c: Dict[str, Any]):
    """(the inner width H x P, the convolution's channels: x with a B and a C a group)."""
    inner = c["mamba_n_heads"] * c["mamba_d_head"]
    assert inner == c["mamba_expand"] * c["hidden_size"], "mamba_expand x hidden_size is the heads' width"
    return inner, inner + 2 * c["mamba_n_groups"] * c["mamba_d_state"]


def layer_params(c: Dict[str, Any], kind: str) -> Dict[str, int]:
    """One layer's parameters by part. `mamba`: W_in (z | xBC | dt), W_out, the convolution's taps and bias, `A_log`,
    `D` and `dt_bias` a head, the gated norm's scale; `attention`: the four projections; both: the SwiGLU's three
    matrices (`shared_intermediate_size`) and two norms."""
    d, ff = c["hidden_size"], c["shared_intermediate_size"]
    parts = {"mlp": 3 * d * ff, "norms": 2 * d}
    if kind == MAMBA:
        inner, channels = _mamba_widths(c)
        heads = c["mamba_n_heads"]
        parts.update(w_in=d * (inner + channels + heads), w_out=inner * d, conv=(c["mamba_d_conv"] + 1) * channels,
                     heads=3 * heads, gate_norm=inner)
    else:
        head_dim = d // c["num_attention_heads"]
        parts.update(attention=2 * d * d + 2 * d * c["num_key_value_heads"] * head_dim)
    return parts


def num_params(c: Dict[str, Any]) -> int:
    """Every parameter this chip holds, by hand: the layers, the tied table over the vocabulary's slice, the final norm."""
    return (sum(sum(layer_params(c, kind).values()) for kind in c["layer_types"])
            + c["vocab_size"] * c["hidden_size"] + c["hidden_size"])


def matmul_params(c: Dict[str, Any]) -> int:
    """Parameters a token meets as an operand of a matrix multiplication: a mamba layer's W_in and W_out, an attention
    layer's four projections, the SwiGLU in every layer, and the tied table once, as the head (the embedding is a
    lookup). Taps, biases, norms and the heads' scalars multiply nothing on the MXU's scale."""
    per_kind = {kind: sum(n for part, n in layer_params(c, kind).items() if part in ("mlp", "w_in", "w_out", "attention"))
                for kind in (MAMBA, ATTENTION)}
    return sum(per_kind[kind] for kind in c["layer_types"]) + c["vocab_size"] * c["hidden_size"]


def ssd_flops_per_token(c: Dict[str, Any], chunk: Optional[int] = None, backward: bool = True) -> float:
    """FLOPs the SSD scan asks for a token and head in the chunked form at `chunk` positions (2 a multiply-add).
    Forward: C B^T once a group (2 chunk N over the group's heads), the masked product against the values (2 chunk P)
    and the two products against the state, Q S and K^T V (2 N P each). Backward: C B^T again (the kernel keeps the
    chunks' states, not the scores), dO V^T and P^T dO (2 chunk P each), four products against the state's shape (K dS,
    V dS^T, dO S^T, Q^T dO) and the two of the scores' gradient summed over a group's heads (2 chunk N each, once a
    group). A product made a head where the mathematics asks for it once a group is the kernel's choice, not counted."""
    chunk = chunk or c["ssd_chunk"]
    n, p = c["mamba_d_state"], c["mamba_d_head"]
    a_group = c["mamba_n_heads"] // c["mamba_n_groups"]
    by_state, by_values, by_keys = 2 * n * p, 2 * chunk * p, 2 * chunk * n / a_group
    forward = by_keys + by_values + 2 * by_state
    return float(forward + (by_keys + 2 * by_values + 4 * by_state + 2 * by_keys if backward else 0))


def ssd_flops_per_step(c: Dict[str, Any], rows: int, seq: int, chunk: Optional[int] = None) -> float:
    """`ssd_flops_per_token` over the device's rows, the heads and the mamba layers."""
    return ssd_flops_per_token(c, chunk) * rows * seq * c["mamba_n_heads"] * _layers(c)[MAMBA]


def ssd_bytes_per_step(c: Dict[str, Any], rows: int, seq: int, chunk: Optional[int] = None) -> float:
    """Bytes the two kernels must move a step, each array read or written once a pass: forward reads v = dt x (a head)
    and B, C (a group) in the activations' type and the log decay a head in f32, writes o and the state every chunk
    starts from (N x P f32 a head and chunk); backward reads all of those but o, and o's gradient, and writes the
    gradients of v, B, C and the decay."""
    chunk = chunk or c["ssd_chunk"]
    act = {"bfloat16": 2, "float32": 4}[c["dtype"]]
    heads, groups, n, p = c["mamba_n_heads"], c["mamba_n_groups"], c["mamba_d_state"], c["mamba_d_head"]
    a_token = act * (4 * heads * p + 4 * groups * n) + 4 * 3 * heads  # v, o, do, dv; B, C, dB, dC; g twice and dg
    states = 2 * heads * n * p * 4 / chunk  # written forward, read backward
    return float(a_token + states) * rows * seq * _layers(c)[MAMBA]


def train_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    """FLOPs the forward and backward passes require per token: 6 per matmul parameter, attention over the causal half
    of `seq` positions in the attention layers (12 * heads * head_dim * seq / 2 each: the kept pairs, as the flash
    functions count them), and the scan's products in the mamba layers. Recomputation is not counted."""
    return (6.0 * matmul_params(c) + 6.0 * _layers(c)[ATTENTION] * c["hidden_size"] * (seq + 1)
            + ssd_flops_per_token(c) * c["mamba_n_heads"] * _layers(c)[MAMBA])


def flash_flops_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """FLOPs the attention layers' flash kernels of one train step require: two products forward and four backward,
    2 x head_dim a kept (query, key) pair, over the triangle seq (seq + 1) / 2, for every query head."""
    head_dim = c["hidden_size"] // c["num_attention_heads"]
    return 12.0 * head_dim * (seq * (seq + 1) // 2) * rows * c["num_attention_heads"] * _layers(c)[ATTENTION]


def flash_bytes_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """Bytes the two kernels must move: a query head's q, o forward and q, o, do, dq backward with three rows of
    statistics; a key/value head's k, v forward and k, v, dk, dv backward (bf16; the statistics f32)."""
    head_dim = c["hidden_size"] // c["num_attention_heads"]
    act, stat = seq * head_dim * 2, seq * 4
    per_call = c["num_attention_heads"] * (6 * act + 3 * stat) + c["num_key_value_heads"] * 6 * act
    return float(per_call) * rows * _layers(c)[ATTENTION]


# ---------------------------------------------------------------------- system
def granite_hybrid_config(c: Dict[str, Any]):
    import jax.numpy as jnp

    from ray_tpu.models.granite_hybrid import GraniteHybridConfig

    assert c["attention_bias"] is False and c["mamba_proj_bias"] is False and c["mamba_conv_bias"] is True
    assert c["tie_word_embeddings"] is True and c["position_embedding_type"] == "nope", "the only form written"
    assert c["num_local_experts"] == 0 and c["num_experts_per_tok"] == 0, "the dense member of the family"
    assert c["hidden_act"] == "silu" and c["normalization_function"] == "rmsnorm"
    _mamba_widths(c)  # mamba_expand agrees with the heads
    return GraniteHybridConfig(
        vocab_size=c["vocab_size"], layer_types=tuple(c["layer_types"]), d_model=c["hidden_size"],
        d_ff=c["shared_intermediate_size"], n_head=c["num_attention_heads"], n_kv_head=c["num_key_value_heads"],
        mamba_heads=c["mamba_n_heads"], mamba_head_dim=c["mamba_d_head"], mamba_state=c["mamba_d_state"],
        mamba_groups=c["mamba_n_groups"], conv_kernel=c["mamba_d_conv"], ssd_chunk=c["ssd_chunk"],
        embedding_multiplier=float(c["embedding_multiplier"]), attention_multiplier=float(c["attention_multiplier"]),
        residual_multiplier=float(c["residual_multiplier"]), logits_scaling=float(c["logits_scaling"]),
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
        self.cfg = granite_hybrid_config(c)
        self.optimizer = default_optimizer(
            learning_rate=c["learning_rate"], warmup_steps=c.get("warmup_steps", 0),
            total_steps=c.get("total_steps", 0))
        self.state = create_train_state(self.cfg, jax.random.PRNGKey(seed), self.optimizer, mesh=mesh)
        self.step = make_train_step(self.cfg, self.optimizer, mesh=mesh)

    def attention_path(self, rows_per_device: int, seq: int, platform: str) -> str:
        """"pallas" where the attention layer runs the flash kernels and the mamba layers the scan's."""
        from ray_tpu.ops import ssd
        from ray_tpu.ops.flash_attention import select_backend

        flash = select_backend((rows_per_device, self.cfg.n_head, seq, self.cfg.head_dim), platform)
        return flash if ssd.select_backend(platform) == "pallas" else "xla"


def build(c: Dict[str, Any], mesh, seed: int) -> System:
    return System(c, mesh, seed)


# ------------------------------------------------------------------- reference
RECURRENCE_BLOCK = 64  # positions the recurrence's backward pass makes again at a time
HEAD_BLOCKS = 8  # the head's logits are made a block of positions at a time


def rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def causal_conv(z, taps, bias):
    """z (batch, seq, channels) against `taps` (kernel, channels) and `bias` (channels): depthwise and causal, tap j
    on position t - (kernel - 1) + j, zeros before the row's first position. Written as the sum over the shifted copies."""
    import jax.numpy as jnp

    kernel, seq = taps.shape[0], z.shape[1]
    out = jnp.zeros_like(z) + bias
    for j in range(kernel):
        back = kernel - 1 - j
        out = out + taps[j] * jnp.concatenate([jnp.zeros_like(z[:, :back]), z[:, :seq - back]], axis=1)
    return out


def ssd_recurrence(x, b, c, dt, a, d, block: int = RECURRENCE_BLOCK):
    """One head of one row, position by position: x (seq, P), b and c (seq, N), dt (seq,) after its softplus, `a` < 0
    and `d` numbers. `S = exp(dt_t a) S + b_t (dt_t x_t)^T`, `y_t = S^T c_t + d x_t`, S (N, P) zero before the first
    position. Returns (y (seq, P), the state after the last position). The positions run in blocks of `block`, each
    made again in the backward pass (`jax.checkpoint`): that pass holds a state a block and a block's states, not one
    a position. The arithmetic is the recurrence's, in the order written."""
    import jax
    import jax.numpy as jnp

    seq = x.shape[0]
    pad = -seq % block
    if pad:  # x = 0, dt = 0: no write, no decay; the outputs there are cut off
        x, b, c = (jnp.concatenate([z, jnp.zeros((pad, z.shape[1]), z.dtype)]) for z in (x, b, c))
        dt = jnp.concatenate([dt, jnp.zeros((pad,), dt.dtype)])

    def position(s, at):
        x_t, b_t, c_t, dt_t = at
        s = jnp.exp(dt_t * a) * s + jnp.outer(b_t, dt_t * x_t)
        return s, s.T @ c_t + d * x_t

    @jax.checkpoint
    def positions_of_a_block(s, xs):
        return jax.lax.scan(position, s, xs)

    blocks = tuple(z.reshape(-1, block, *z.shape[1:]) for z in (x, b, c, dt))
    s, y = jax.lax.scan(positions_of_a_block, jnp.zeros((b.shape[1], x.shape[1]), x.dtype), blocks)
    return y.reshape(-1, x.shape[1])[:seq], s


def layers_in_order(blocks, c: Dict[str, Any]):
    """(kind, the layer's own parameters) of every layer in the published order, out of the tree the system trains:
    one stack for every place in the period, the same place of every period on its leading axis."""
    import jax

    n_periods = jax.tree.leaves(blocks["period"])[0].shape[0]
    own = [jax.tree.map(lambda a, p=p: a[p], place) for p in range(n_periods) for place in blocks["period"]]
    assert len(own) == len(c["layer_types"]) and not blocks["leading"] and not blocks["trailing"]
    return list(zip(c["layer_types"], own))


def reference_loss(params, tokens, c: Dict[str, Any], dtype=None):
    """granite-4.0-h (the equations of ISSUE 73 and `models/granite_hybrid.py`'s docstring; the source's `config.json`
    fixes the sizes and the four multipliers, Dao & Gu 2024 and HF's `GraniteMoeHybrid` / Bamba mixer the rest) in
    float32 `jax.numpy`; returns (loss, {"decay_log_min": the most negative sum of `dt A` over `ssd_chunk`
    consecutive positions (a chunk of the program's walk) in any head of any mamba layer, "state": the first mamba
    layer's states (batch, heads, N, P) after the rows' last position}).

    Pre-norm block with the residual multiplier on both branches: `h = x + r mixer(N(x))`, `y = h + r mlp(N(h))`,
    `mlp(n) = W_d (silu(W_g n) * W_u n)`, RMSNorm at `rms_norm_eps`. A `mamba` mixer: `[z | xBC | dt] = n W_in`;
    `xBC = silu(conv(xBC) + bias)` (`causal_conv`); x cut into `mamba_n_heads` heads of `mamba_d_head`, B and C into
    `mamba_n_groups` groups of `mamba_d_state`, a group shared by its consecutive heads; `dt = softplus(dt +
    dt_bias)`; the recurrence position by position (`ssd_recurrence`) with `A = -exp(A_log)` and the skip `D x`; the
    output times `silu(z)`, then RMS-normed over all its channels with a scale of that width (the gate before the
    norm), then `W_out`. An `attention` mixer: `num_attention_heads` query heads on `num_key_value_heads` key/value
    heads, no rotation, causal softmax of the scores times `attention_multiplier` (1/64, not 64^-1/2), `W_o`. The
    embedding's rows times `embedding_multiplier`; a final norm; logits against the same table over
    `logits_scaling`; mean cross entropy of the next token. No kernel, no chunked form, no bf16.

    Takes the parameter tree the system trains (`layers_in_order`; every matrix as (in, out), W_in as its three
    column blocks `w_z`, `w_xbc`, `w_dt`, the SwiGLU's input matrix as `w_gate`, `w_up`, a convolution's taps as
    (kernel, channels)). Departures from a line-by-line transcription, none changes the arithmetic: each layer,
    each attention head and each block of the recurrence is made again in the backward pass (`jax.checkpoint`); the
    head's logits and their cross entropy are made `HEAD_BLOCKS` blocks of positions at a time.

    `dtype` (default float32) computes everything, parameters, state, decay and logits included, in that type
    instead: what a lower precision than the configuration states would give, for PERF.md's second reading."""
    import jax
    import jax.numpy as jnp

    f = jnp.dtype(dtype or jnp.float32)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    batch, seq = inputs.shape
    d, eps, r = c["hidden_size"], c["rms_norm_eps"], jnp.asarray(c["residual_multiplier"], f)
    n_head, n_kv = c["num_attention_heads"], c["num_key_value_heads"]
    head_dim = d // n_head
    heads, p, groups, n = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_n_groups"], c["mamba_d_state"]
    inner = heads * p
    mask = jnp.tril(jnp.ones((seq, seq), bool))

    @jax.checkpoint
    def one_head(q, k, v):  # (batch, seq, head_dim) each
        scores = jnp.einsum("bqh,bkh->bqk", q, k) * jnp.asarray(c["attention_multiplier"], f)
        scores = jnp.where(mask, scores, -jnp.inf)
        return jnp.einsum("bqk,bkh->bqh", jax.nn.softmax(scores, axis=-1), v)

    def attention(x, layer):
        by_head = lambda z, w: jnp.einsum("bsd,dnh->nbsh", z, w)  # noqa: E731
        q, k, v = (by_head(x, layer[w]) for w in ("wq", "wk", "wv"))
        k, v = (jnp.repeat(z, n_head // n_kv, axis=0) for z in (k, v))  # a key/value head for each of its query heads
        out = jax.lax.map(lambda qkv: one_head(*qkv), (q, k, v))  # (heads, batch, seq, head_dim)
        return jnp.einsum("nbsh,nhd->bsd", out, layer["wo"]), None

    def mamba(x, layer):
        z, dt = x @ layer["w_z"], x @ layer["w_dt"]
        xbc = jax.nn.silu(causal_conv(x @ layer["w_xbc"], layer["conv_w"], layer["conv_b"]))
        xs = xbc[..., :inner].reshape(batch, seq, heads, p)
        b, cc = (xbc[..., at:at + groups * n].reshape(batch, seq, groups, n) for at in (inner, inner + groups * n))
        b, cc = (jnp.repeat(g, heads // groups, axis=2) for g in (b, cc))  # a group for each of its heads
        dt = jax.nn.softplus(dt + layer["dt_bias"])  # (batch, seq, heads)
        a = -jnp.exp(layer["A_log"])
        a_head = jax.vmap(ssd_recurrence, in_axes=(1, 1, 1, 1, 0, 0), out_axes=(1, 0))
        y, states = jax.vmap(a_head, in_axes=(0, 0, 0, 0, None, None))(xs, b, cc, dt, a, layer["D"])
        y = y.reshape(batch, seq, inner) * jax.nn.silu(z)
        log_decay = jnp.cumsum(dt * a, axis=1)  # (batch, seq, heads)
        chunk = min(c["ssd_chunk"], seq)
        over_a_chunk = log_decay[:, chunk - 1:] - jnp.concatenate(
            [jnp.zeros_like(log_decay[:, :1]), log_decay[:, :seq - chunk]], axis=1)
        stats = (over_a_chunk.min(), states)
        return rms_norm(y, layer["gate_norm"], eps) @ layer["w_out"], stats

    def block(kind):
        @jax.checkpoint
        def apply(x, layer):
            layer = jax.tree.map(lambda w: w.astype(f), layer)
            mixed, stats = (mamba if kind == MAMBA else attention)(rms_norm(x, layer["mixer_norm"], eps), layer)
            h = x + r * mixed
            m = rms_norm(h, layer["mlp_norm"], eps)
            return h + r * ((jax.nn.silu(m @ layer["w_gate"]) * (m @ layer["w_up"])) @ layer["w_down"]), stats
        return apply

    @jax.checkpoint
    def head_block(table, xs):
        x, t = xs  # (batch, positions, d), (batch, positions)
        logp = jax.nn.log_softmax(x @ table.T / jnp.asarray(c["logits_scaling"], f), axis=-1)
        return -jnp.take_along_axis(logp, t[..., None], axis=-1).sum()

    with jax.default_matmul_precision("highest"):
        table = params["embed"].astype(f)
        x = table[inputs] * jnp.asarray(c["embedding_multiplier"], f)
        least, states = [], []
        for kind, layer in layers_in_order(params["blocks"], c):
            x, stats = block(kind)(x, layer)
            if stats is not None:
                least.append(stats[0])
                states.append(stats[1])
        x = rms_norm(x, params["final_norm"].astype(f), eps)
        blocks = HEAD_BLOCKS if seq % HEAD_BLOCKS == 0 else 1
        by_block = lambda z: jnp.moveaxis(z.reshape(batch, blocks, seq // blocks, *z.shape[2:]), 1, 0)  # noqa: E731
        total = jax.lax.map(lambda xs: head_block(table, xs), (by_block(x), by_block(targets))).sum()
        loss = (total / (batch * seq)).astype(jnp.float32)
        return loss, {"decay_log_min": jnp.stack(least).min().astype(jnp.float32),
                      "state": jax.lax.stop_gradient(states[0]).astype(jnp.float32)}


# Tolerances of the agreement between the system (bf16 activations and matmul operands, the flash kernels and the scan's
# kernels in chunks of `ssd_chunk` with an f32 state, decay and dt; f32 norms, logits and parameters) and the
# reference (f32 throughout, the recurrence position by position), at seeded initial weights, on the one row (4,096
# tokens) of the run's first batch that the harness hands `check`: the timed shape. Measured on the chip at the published
# widths under the cell's own traffic (my chip runs, PR 73, PERF.md section 6: `tools/granite_hybrid_readings.py` and
# the cell's own runs): 10 readings of the system, each its own seed; 2 of the reference itself with parameters, state,
# decay, dt, norms and logits in bf16, the nearest precision below the configuration's ("below"); 2 each of the system
# with the running log-decay, the state a chunk starts from, and dt rounded to bf16 inside the scan; 1 of each of the
# four multipliers set to 1:
#   loss            system off by 2.9e-6..6.2e-5 (a loss of 9.442 over 12,544 words); below 9.3e-3, 9.7e-3: the limit is
#                   sixteen times the system's largest reading and a ninth of below's smallest. `logits_scaling` 1: 20.0
#   gradient norm   system 8.6e-4..1.02e-3 on every seed (its own bias: the bf16 backward reads 0.1 % short); below
#                   5.2e-3, 7.9e-3: two and a half times the largest reading, under half of below's smallest.
#                   `embedding_multiplier` 1: 2.6; `residual_multiplier` 1: 1.9; `attention_multiplier` 1: 5.1e-2
# The gradient at a leaf is the distance `|system - reference|` over `|reference|` (not a difference of norms: a
# gradient that points elsewhere at the right length is told): of the first mamba layer what only the scan's backward
# pass reaches (`w_xbc`: dx, dB, dC through the convolution; `w_dt`, `dt_bias`: ddt through the decay and through v =
# dt x; `A_log`; `D`; `conv_b`), and of the attention layer `wq`, whose gradient is linear in `attention_multiplier`.
# Every leaf reads about 0.022 on every seed: what bf16 activations leave of a gradient ten layers deep.
#   w_xbc           system 0.0202..0.0244; decay bf16 0.0448, 0.0449; below 0.122, 0.143: **the limit that tells a decay
#                   kept in bf16 inside the scan**, half again the largest reading and a sixth under decay bf16's
#   w_dt            system 0.0193..0.0300; decay bf16 0.0761, 0.0766; below 0.129, 0.154: as above, two thirds again
#                   over the largest reading and a third under decay bf16's
#   wq              system 0.0213..0.0238; below 0.070, 0.081; `attention_multiplier` 1: 370
#   D, conv_b       64 and 4,352 numbers: system 0.017..0.0253 and 0.0202..0.0257; below 0.098, 0.146 and 0.187, 0.207
#   dt_bias, A_log  64 numbers each, each a sum of 4,096 signed terms: system 0.0166..0.0519 and 0.0138..0.0552;
#                   below 0.286, 0.441 and 0.441, 0.559: the limit is three times the largest reading, for another
#                   function (the decay left out), nothing finer
#   state           `|S - S_ref| / |S_ref|` of the first mamba layer's states behind the row (64 heads of 128 x 64):
#                   system 3.8e-3..2.0e-2 by the seed (the heads that die inside a chunk hold the last positions' bf16
#                   x and B alone); decay bf16 3.6e-2, 8.3e-2; below 0.37, 0.41: two and a half times the largest
#                   reading, for a state that is another function's
# What none of them tells at these widths is the state a chunk hands on, or dt, rounded to bf16 (every reading inside
# the system's own range: w_dt 0.0223..0.0247, state 4.2e-3, 2.0e-2): 2^-9 of either is what every bf16 activation
# round it already carries. Those faults are held where they can be told, in float32 on the CPU (`tests/test_ssd.py
# test_a_bf16_state_decay_or_dt_is_told`: ten times the form's own limit), and PERF.md section 7 has the row. No
# comparison of losses can see parameters kept in bf16: the parameters' and the moments' dtype is checked by name.
LOSS_ABS_TOL = 1e-3
GRAD_NORM_REL_TOL = 2.5e-3
LEAF_GRAD_REL_TOL = {"w_xbc": 0.038, "w_dt": 0.05, "dt_bias": 0.15, "A_log": 0.15, "D": 0.06, "conv_b": 0.06, "wq": 0.045}
STATE_REL_TOL = 5e-2
CHECKED_LEAVES = ("w_xbc", "w_dt", "dt_bias", "A_log", "D", "conv_b", "wq")


def _checked(grads, c: Dict[str, Any]):
    """The gradient at each of `CHECKED_LEAVES`: of the first mamba layer, `wq` of the first attention layer."""
    first = {kind: c["layer_types"].index(kind) for kind in (MAMBA, ATTENTION)}
    place = lambda name: grads["blocks"]["period"][first[ATTENTION if name == "wq" else MAMBA]][name][0]  # noqa: E731
    return [place(name) for name in CHECKED_LEAVES]


def losses_and_grads(system: System, dtype=None):
    """(of_system, of_reference): each `(params, tokens) -> (loss, the gradient's global norm, its values at the
    checked leaves, statistics)`, a program each so that the two gradient trees (3.1 GB each at the published
    widths) are never held at once. The system's statistics: `state`, the first mamba layer's states as its own
    kernels hand them on (2 MB at the published widths); the reference's: `reference_loss`'s."""
    import jax
    import optax

    from ray_tpu.models import granite_hybrid

    cfg, mesh, c = system.cfg, system.mesh, system.c

    def of_system(params, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: granite_hybrid.loss_fn(p, {"tokens": tokens}, cfg, mesh=mesh))(params)
        return loss, optax.global_norm(grads), _checked(grads, c), {
            "state": granite_hybrid.first_state(params, tokens[:, :-1], cfg)}

    def of_reference(params, tokens):
        (loss, stats), grads = jax.value_and_grad(
            lambda p: reference_loss(p, tokens, c, dtype), has_aux=True)(params)
        return loss, optax.global_norm(grads), _checked(grads, c), stats

    return of_system, of_reference


@contextlib.contextmanager
def _moments_set_aside(system: System):
    """Before the first step AdamW's moments are zeros: they are dropped for the length of the block and made again.
    At the published widths they are 6.18 GB, and the check's two programs do not fit beside them and the
    parameters; the step, which updates them, does. A state that has taken a step keeps its moments."""
    import jax

    state = system.state
    if int(state.step) != 0 or not jax.tree.leaves(state.opt_state):
        yield
        return
    state.opt_state = None
    try:
        yield
    finally:
        state.opt_state = jax.jit(system.optimizer.init)(state.params)


def check(system: System, tokens, *, program=None, reference=None) -> Dict[str, Any]:
    """Loss, global gradient norm, the gradient at seven leaves and the first mamba layer's states, of the system's
    `loss_fn` (through the flash kernels and the scan's) against the reference's, on `tokens` (a jax array, already
    placed) with the run's own parameters. Nothing is gathered to the host but scalars. A limit is the
    configuration's own (`check_tolerances`: the rehearsal's toy, whose sums run over 64 terms), else this file's.
    `program`, `(params, tokens) -> what `losses_and_grads`' first gives`, stands in the system's place
    (`tools/granite_hybrid_readings.py`: the reference a precision below, the system under a planted fault), and
    `reference` is what the reference's program gave for these tokens where the caller has run it already."""
    import jax
    import jax.numpy as jnp

    own = system.c.get("check_tolerances", {})
    loss_tol, grad_tol = own.get("loss_abs", LOSS_ABS_TOL), own.get("grad_norm_rel", GRAD_NORM_REL_TOL)
    leaf_tol = own.get("leaf_grad_rel", LEAF_GRAD_REL_TOL)
    if not isinstance(leaf_tol, dict):
        leaf_tol = dict.fromkeys(CHECKED_LEAVES, leaf_tol)  # one limit for every leaf
    state_tol = own.get("state_rel", STATE_REL_TOL)
    params = system.state.params
    want_dtype = jnp.dtype(system.c["param_dtype"])
    leaves = jax.tree.leaves(params) + [
        x for x in jax.tree.leaves(system.state.opt_state) if getattr(x, "ndim", 0) > 0]
    wrong_dtype = sorted({str(x.dtype) for x in leaves if x.dtype != want_dtype})
    del leaves
    of_system, of_reference = losses_and_grads(system)

    def distances(mine, reference):
        """`|mine - reference| / |reference|` of each pair, and the root mean square of each of the last pair."""
        norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))  # noqa: E731
        rms = lambda x: norm(x) / x.size ** 0.5  # noqa: E731
        return ([norm(a.astype(jnp.float32) - b.astype(jnp.float32)) / jnp.maximum(norm(b), 1e-30)
                 for a, b in zip(mine, reference)], rms(mine[-1]), rms(reference[-1]))

    with _moments_set_aside(system):
        if reference is None:
            reference = jax.jit(of_reference)(params, tokens)
        ref_loss, ref_norm, ref_leaves, ref_stats = reference
        sys_loss, sys_norm, sys_leaves, sys_stats = jax.jit(program or of_system)(params, tokens)
        (*leaf_err, state_err), sys_rms, ref_rms = jax.device_get(jax.jit(distances)(
            [*sys_leaves, sys_stats["state"]], [*ref_leaves, ref_stats["state"]]))
        del sys_leaves, sys_stats
    sys_loss, sys_norm, ref_loss, ref_norm, decay_log_min = jax.device_get(
        (sys_loss, sys_norm, ref_loss, ref_norm, ref_stats["decay_log_min"]))
    rel = lambda a, b: abs(float(a) - float(b)) / max(abs(float(b)), 1e-30)  # noqa: E731
    out = {
        "loss_system": float(sys_loss), "loss_reference": float(ref_loss),
        "grad_norm_system": float(sys_norm), "grad_norm_reference": float(ref_norm),
        "loss_abs_err": abs(float(sys_loss) - float(ref_loss)),
        "grad_norm_rel_err": rel(sys_norm, ref_norm),
        "leaf_grad_rel_err": {name: float(err) for name, err in zip(CHECKED_LEAVES, leaf_err)},
        "ssd.decay_log_min": float(decay_log_min),
        "ssd.state_rms": {"system": float(sys_rms), "reference": float(ref_rms)},
        "state_rel_err": float(state_err),
        "state_dtypes_other_than_stated": wrong_dtype,
        "limits": {"loss_abs_err": loss_tol, "grad_norm_rel_err": grad_tol, "leaf_grad_rel_err": leaf_tol,
                   "state_rel_err": state_tol},
    }
    got = [out["loss_system"], out["loss_reference"], out["grad_norm_system"], out["grad_norm_reference"],
           out["state_rel_err"], *out["leaf_grad_rel_err"].values()]
    out["ok"] = bool(
        all(map(math.isfinite, got)) and out["loss_abs_err"] <= loss_tol and out["grad_norm_rel_err"] <= grad_tol
        and out["state_rel_err"] <= state_tol and not wrong_dtype
        and all(err <= leaf_tol[name] for name, err in out["leaf_grad_rel_err"].items()))
    return out
