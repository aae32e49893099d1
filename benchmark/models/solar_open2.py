"""Solar Open 2 for the benchmark: the system under test built through ray_tpu's
public API, a plain float32 reference written from the layer's equations, the
comparison that decides `correct`, and the arithmetic of FLOPs and bytes.

A configuration file (`benchmark/configs/<name>.json`) with `"model":
"solar_open2"` is served by this module. Keys read, under the names of the
source's `config.json`: `num_hidden_layers`, `gqa_layers`, `hidden_size`,
`num_attention_heads`, `num_key_value_heads`, `head_dim`, `linear_attn_config`
(`num_heads`, `num_kv_heads`, `head_dim`, `short_conv_kernel_size`),
`kda_allow_neg_eigval`, `kda_use_full_proj`, `use_gqa_gate`, `use_rope`,
`intermediate_size` (kept, used by no layer), `moe_intermediate_size`,
`n_routed_experts` (the experts held here; the router's width is
`published.n_routed_experts` where the file cuts the key), `n_shared_experts`,
`num_experts_per_tok`, `norm_topk_prob`, `routed_scaling_factor`,
`first_k_dense_replace`, `vocab_size`, `max_position_embeddings`,
`rms_norm_eps`, `tie_word_embeddings`; and the benchmark's own:
`first_expert_held`, `kda_gate_rank`, `dtype`,
`param_dtype`, `remat_policy`, `attention`, `learning_rate` (the peak),
`warmup_steps` and `total_steps`. Where the file holds a share of the heads
(`num_attention_heads`, `num_key_value_heads`, `linear_attn_config.num_heads`
under `reduced`) the counts are the heads built here.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

from benchmark.models.olmo_hybrid import _moments_set_aside  # AdamW's zero moments out of the check's way: 6.7 GB here

GQA, KDA = "gqa", "kda"
KDA_CHUNK = 128  # `ops/gated_delta_rule.py CHUNK`, which the kernels' scope declares (`chunk_128`); a test holds the two equal

# ------------------------------------------------------------------ arithmetic
# No jax below this line until `build`: the parent and the tests use these.
# Everything counts what this chip computes: the heads it builds, the experts
# it holds, the slice of the vocabulary it holds, the layers it holds.


def layer_types(c: Dict[str, Any]) -> List[str]:
    return [GQA if i in c["gqa_layers"] else KDA for i in range(c["num_hidden_layers"])]


def _layers(c: Dict[str, Any]) -> Dict[str, int]:
    types = layer_types(c)
    return {"kda": types.count(KDA), "gqa": types.count(GQA), "moe": len(types)}


def router_width(c: Dict[str, Any]) -> int:
    """The experts the router scores: the published count where the file's
    `n_routed_experts` is the chip's share of them."""
    return c.get("published", {}).get("n_routed_experts", c["n_routed_experts"])


def held_pairs_per_layer(c: Dict[str, Any], tokens: int) -> float:
    """The (token, expert) pairs an even router gives the experts held here."""
    return tokens * c["num_experts_per_tok"] * c["n_routed_experts"] / router_width(c)


def _linear(c: Dict[str, Any]):
    """(heads built here, d_k = d_v, the convolution's taps) of a linear layer."""
    lin = c["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]


def layer_params(c: Dict[str, Any], kind: str) -> Dict[str, int]:
    """One layer's parameters here by part: the mixer of `kind`, `moe` (two norms, the router whole, the
    shared expert whole, the routed experts held)."""
    d, f, rank = c["hidden_size"], c["moe_intermediate_size"], c["kda_gate_rank"]
    moe = 2 * d + d * router_width(c) + 3 * d * f * (c["n_shared_experts"] + c["n_routed_experts"])
    if kind == KDA:
        h, hd, taps = _linear(c)
        wide = h * hd
        mixer = (4 * d * wide + 2 * (d * rank + rank * wide) + wide  # q, k, v, o; the two gates, one's bias
                 + d * h + 3 * taps * wide + h + wide + hd)  # w_b; the taps; A_log, dt_bias, the head norm
    else:
        q, kv = c["num_attention_heads"] * c["head_dim"], c["num_key_value_heads"] * c["head_dim"]
        mixer = 3 * d * q + 2 * d * kv  # q, the gate, o; k, v
    return {"mixer": mixer, "moe": moe}


def num_params(c: Dict[str, Any]) -> int:
    return (2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"]
            + sum(sum(layer_params(c, kind).values()) for kind in layer_types(c)))


def active_matmul_params(c: Dict[str, Any]) -> float:
    """Parameters one token meets here as an operand of a matrix multiplication: a linear layer's q, k, v and
    output projections and its two low-rank gates, an attention layer's q, k, v, gate and output projections,
    in every layer the router, the shared expert and the three matrices of each routed expert a token's pairs
    meet on this chip (`num_experts_per_tok` x held / routed over, in expectation), and the untied head over
    the vocabulary's slice. The embedding is a lookup; `w_b`'s column a head, the taps and the norms multiply
    nothing on the MXU's scale."""
    d, f, rank, n = c["hidden_size"], c["moe_intermediate_size"], c["kda_gate_rank"], _layers(c)
    h, hd, _ = _linear(c)
    q, kv = c["num_attention_heads"] * c["head_dim"], c["num_key_value_heads"] * c["head_dim"]
    return (n["kda"] * (4 * d * h * hd + 2 * (d * rank + rank * h * hd)) + n["gqa"] * (3 * d * q + 2 * d * kv)
            + n["moe"] * (d * router_width(c) + 3 * d * f * (c["n_shared_experts"] + held_pairs_per_layer(c, 1)))
            + c["vocab_size"] * d)


def kda_flops_per_token(c: Dict[str, Any], chunk: int = KDA_CHUNK, backward: bool = True) -> float:
    """FLOPs the channel-wise delta rule asks for a token and head in the chunked form at `chunk` positions (2
    a multiply-add), counted as `olmo_hybrid.gdn_flops_per_token` counts the scalar rule's: forward the two
    pairwise terms (2 C d_k each: a decay inside the contraction is one multiply-add a term still), K S, Q S
    and K^T N (2 d_k d_v each), T R and P N (2 C d_v each) and the triangular solve behind T (C^2); backward
    the forward's first four again, six further products against the state's shape, four against C x d_v and
    four against C x d_k. How the kernel makes a pairwise term without an exponent above 0 (`log2 C` masked
    products, each of two f32 operands) and T (the doubling) is its choice and is not counted."""
    _, hd, _ = _linear(c)
    by_state, by_keys, by_values, solve = 2 * hd * hd, 2 * chunk * hd, 2 * chunk * hd, chunk * chunk
    forward = 2 * by_keys + 3 * by_state + 2 * by_values + solve
    if not backward:
        return float(forward)
    return float(forward + (2 + 4) * by_keys + (1 + 6) * by_state + (1 + 4) * by_values + solve)


def kda_flops_per_step(c: Dict[str, Any], rows: int, seq: int, chunk: int = KDA_CHUNK) -> float:
    """`kda_flops_per_token` over the device's rows, the heads built here and the linear layers."""
    return kda_flops_per_token(c, chunk) * rows * seq * _linear(c)[0] * _layers(c)["kda"]


def kda_bytes_per_step(c: Dict[str, Any], rows: int, seq: int, chunk: int = KDA_CHUNK) -> float:
    """Bytes the two kernels must move a step: forward reads q, k, v and writes o; backward reads q, k, v and
    o's gradient and writes the three gradients (the activations' type); the vector gate (d_k a token and head)
    and beta in f32, read by both and their gradients written; the state every chunk starts from (d_k x d_v
    f32 a chunk), written forward and read backward: the backward pass has no other way to a chunk's state."""
    _, hd, _ = _linear(c)
    act = {"bfloat16": 2, "float32": 4}[c["dtype"]]
    per_token = act * (4 * hd + 4 * hd + 3 * hd) + 4 * 3 * (hd + 1) + 2 * 4 * hd * hd / chunk
    return float(per_token) * rows * seq * _linear(c)[0] * _layers(c)["kda"]


def train_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    """FLOPs the forward and backward passes require per token on this chip: 6 per active matmul parameter,
    attention over the full square of `seq` positions by the query heads built here in the attention layers
    (12 * heads * head_dim * seq each, `gpt2.train_flops_per_token`'s convention), and the scan's products in
    the linear layers. Recomputation is not counted."""
    return (6.0 * active_matmul_params(c)
            + 12.0 * _layers(c)["gqa"] * c["num_attention_heads"] * c["head_dim"] * seq
            + kda_flops_per_token(c) * _linear(c)[0] * _layers(c)["kda"])


def flash_flops_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """FLOPs the causal attention of one train step requires of the two flash kernels: per (row, query head)
    two products forward and four backward, each 2 * seq^2 * head_dim, over the causal half of the square
    (`gpt2.flash_flops_per_step`), for the attention layers alone."""
    return 6 * 2 * seq * seq * c["head_dim"] / 2 * rows * c["num_attention_heads"] * _layers(c)["gqa"]


def flash_bytes_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """Bytes the two kernels must move a step: q, o, do, dq a query head, k, v, dk, dv a key/value head (bf16),
    the row statistics and delta (f32), as `keye_vl2.flash_bytes_per_step` counts grouped heads."""
    act, stat = seq * c["head_dim"] * 2, seq * 4
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    per_row = nh * (2 * act + stat) + nkv * 2 * act + nh * (4 * act + 2 * stat) + nkv * 4 * act
    return float(per_row * rows * _layers(c)["gqa"])


def moe_expert_flops_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """FLOPs the held experts of one train step require: each pair an even router gives them meets three
    matrices of hidden_size x moe_intermediate_size, 2 FLOPs a parameter forward and 4 backward. The pairs of
    experts held elsewhere are not this chip's; the shared expert is outside the scope `experts`."""
    pairs = held_pairs_per_layer(c, rows * seq)
    return 6.0 * 3 * c["hidden_size"] * c["moe_intermediate_size"] * pairs * _layers(c)["moe"]


def moe_expert_bytes_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """Bytes the nine grouped products of a step must move in bf16: each of the three matmuls reads its held
    rows and every held expert's matrix and writes its result, once forward and once for each of its two
    gradients."""
    pairs = held_pairs_per_layer(c, rows * seq)
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    one_product = pairs * d + c["n_routed_experts"] * d * f + pairs * f
    return 2.0 * 3 * 3 * one_product * _layers(c)["moe"]


# ---------------------------------------------------------------------- system
def solar_open2_config(c: Dict[str, Any]):
    import jax.numpy as jnp

    from ray_tpu.models.solar_open2 import SolarOpen2Config

    lin = c["linear_attn_config"]
    assert c["use_rope"] is False and c["use_gqa_gate"] is True and c["kda_use_full_proj"] is False, "the only form written"
    assert c["tie_word_embeddings"] is False and c["first_k_dense_replace"] == 0 and c["n_shared_experts"] == 1
    assert lin["num_kv_heads"] in (None, lin["num_heads"]), "a key head a value head"
    return SolarOpen2Config(
        vocab_size=c["vocab_size"], layer_types=tuple(layer_types(c)), d_model=c["hidden_size"],
        n_head=c["num_attention_heads"], n_kv_head=c["num_key_value_heads"], head_dim=c["head_dim"],
        linear_heads=lin["num_heads"], linear_key_dim=lin["head_dim"], linear_value_dim=lin["head_dim"],
        conv_kernel=lin["short_conv_kernel_size"], gate_rank=c["kda_gate_rank"],
        allow_neg_eigval=c["kda_allow_neg_eigval"], d_ff=c["intermediate_size"],
        d_expert=c["moe_intermediate_size"], n_experts=router_width(c),
        experts_per_token=c["num_experts_per_tok"], n_experts_held=c["n_routed_experts"],
        first_expert_held=c.get("first_expert_held", 0), norm_topk_prob=c["norm_topk_prob"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
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
        self.cfg = solar_open2_config(c)
        self.optimizer = default_optimizer(
            learning_rate=c["learning_rate"], warmup_steps=c.get("warmup_steps", 0),
            total_steps=c.get("total_steps", 0))
        self.state = create_train_state(self.cfg, jax.random.PRNGKey(seed), self.optimizer, mesh=mesh)
        self.step = make_train_step(self.cfg, self.optimizer, mesh=mesh)

    def attention_path(self, rows_per_device: int, seq: int, platform: str) -> str:
        """"pallas" where the attention layers run the flash kernels and the linear ones the scan's."""
        from ray_tpu.ops import kda
        from ray_tpu.ops.flash_attention import select_backend

        flash = select_backend((rows_per_device, self.cfg.n_head, seq, self.cfg.head_dim), platform)
        return flash if kda.select_backend(platform) == "pallas" else "xla"


def build(c: Dict[str, Any], mesh, seed: int) -> System:
    return System(c, mesh, seed)


# ------------------------------------------------------------------- reference
RECURRENCE_BLOCK = 64  # positions the recurrence's backward pass makes again at a time


def kda_recurrence(q, k, v, g, beta, block: int = RECURRENCE_BLOCK):
    """One head of one row, token by token: q, k (seq, d_k), v (seq, d_v), g (seq, d_k) the log decay of
    every channel, beta (seq,). `S' = Diag(exp(g_t)) S` (row c of the state by `exp(g_t[c])`), `u = beta_t (v_t
    - S'^T k_t)`, `S = S' + k_t u^T`, `o_t = S^T q_t`, S zero before the first token. The positions run in
    blocks of `block`, each made again in the backward pass (`jax.checkpoint`): that pass holds a state a block
    and a block's states, not one a position. The arithmetic is the recurrence's, in the order written."""
    import jax
    import jax.numpy as jnp

    seq = q.shape[0]
    pad = -seq % block
    if pad:  # k = 0, beta = 0, g = 0: no write, no decay; the outputs there are cut off
        q, k, v, g = (jnp.concatenate([x, jnp.zeros((pad, x.shape[1]), x.dtype)]) for x in (q, k, v, g))
        beta = jnp.concatenate([beta, jnp.zeros((pad,), beta.dtype)])

    def token(s, x):
        q_t, k_t, v_t, g_t, beta_t = x
        s = jnp.exp(g_t)[:, None] * s
        u = beta_t * (v_t - s.T @ k_t)
        s = s + jnp.outer(k_t, u)
        return s, s.T @ q_t

    @jax.checkpoint
    def tokens_of_a_block(s, xs):
        return jax.lax.scan(token, s, xs)

    blocks = tuple(x.reshape(-1, block, *x.shape[1:]) for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(tokens_of_a_block, jnp.zeros((k.shape[1], v.shape[1]), q.dtype), blocks)
    return o.reshape(-1, v.shape[1])[:seq]


def rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def causal_conv(z, taps):
    """z (batch, seq, channels) against `taps` (kernel, channels): depthwise and causal, tap j on position t -
    (kernel - 1) + j, zeros before the row's first position, no bias. Written as the sum over the shifted copies."""
    import jax.numpy as jnp

    kernel, seq = taps.shape[0], z.shape[1]
    out = jnp.zeros_like(z)
    for j in range(kernel):
        back = kernel - 1 - j
        out = out + taps[j] * jnp.concatenate([jnp.zeros_like(z[:, :back]), z[:, :seq - back]], axis=1)
    return out


def routing_matrix(scores, k: int, renormalise: bool, scale: float):
    """(tokens, experts): the sigmoid score where the expert is one of the token's k largest, zero elsewhere;
    renormalised over the chosen with the source's `norm_topk_prob`, then times `routed_scaling_factor`."""
    import jax
    import jax.numpy as jnp

    chosen = jax.nn.one_hot(jax.lax.top_k(scores, k)[1], scores.shape[-1], dtype=bool).any(axis=1)
    weights = jnp.where(chosen, scores, 0.0)
    if renormalise:
        weights = weights / weights.sum(-1, keepdims=True)
    return weights * scale, chosen


def layers_in_order(blocks, c: Dict[str, Any]):
    """(kind, the layer's own parameters) of every layer in the published order, out of the tree the system
    trains: one stack for every place in the period, the same place of every period on its leading axis."""
    import jax

    types = layer_types(c)
    n_periods = jax.tree.leaves(blocks["period"])[0].shape[0]
    own = [jax.tree.map(lambda a, p=p: a[p], place) for p in range(n_periods) for place in blocks["period"]]
    assert len(own) == len(types) and not blocks["leading"] and not blocks["trailing"]
    return list(zip(types, own))


HEAD_BLOCKS = 8  # the head's logits are made a block of positions at a time


def reference_loss(params, tokens, c: Dict[str, Any], dtype=None, scan_dtype=None):
    """Solar Open 2 (the equations of ISSUE 59 and `models/solar_open2.py`'s docstring; the source's
    `config.json` fixes the sizes, the Kimi Linear paper the linear layer, what neither gives is under the
    configuration's `assumed`) in float32 `jax.numpy`; returns (loss, {"chosen": (layers, tokens, experts)
    the experts each token was given, "neg_eigval_share": the share of beta_t > 1, "decay_min": the least
    exp(g_t) over the linear layers}).

    Pre-norm block, RMSNorm (eps `rms_norm_eps`), no bias but the output gate's: `h = x + mixer(N(x))`, `y =
    h + moe(N(h))`. A `kda` mixer: q, k, v projections, each through a causal depthwise convolution
    (`causal_conv`) and SiLU; q and k L2-normalised over a head (`x / sqrt(sum x^2 + 1e-6)`), q scaled by
    d_k^-1/2; `g = -exp(A_log) softplus(W_f_up (W_f_down n) + dt_bias)`, a vector of d_k a head; `beta = 2
    sigmoid(w_b . n)`; the recurrence token by token (`kda_recurrence`); the output RMS-normed over a head's
    d_v with one scale of that width, times `sigmoid(W_g_up (W_g_down n) + b_g)`, then `W_o`. A `gqa` mixer:
    the query heads built here on their key/value heads, no rotation, causal softmax at head_dim^-1/2, the
    output times `sigmoid(W_gate n)` element by element, `W_o`. The expert layer: `s = sigmoid(W_r n)`, the
    `num_experts_per_tok` largest, weights `s` at the chosen over their sum (`routing_matrix`), `sum_e w_e W2_e
    (silu(W1_e n) * W3_e n)` over the experts this chip holds in a loop over them, every held expert applied to
    every token and weighted by the routing matrix, which is zero where the expert was not chosen; plus the
    shared expert on every token. Final RMSNorm, an untied head, mean cross entropy of the next token. No kernel, no chunked form, no sort, no bf16.

    Takes the parameter tree the system trains (`layers_in_order`). Departures from a line-by-line
    transcription, none changes the arithmetic: each layer, the attention's group, each expert and each block
    of the recurrence is made again in the backward pass (`jax.checkpoint`); the head's logits and their cross
    entropy are made `HEAD_BLOCKS` blocks of positions at a time.

    `dtype` (default float32) computes everything, parameters, state, decay and logits included, in that type
    instead: what a lower precision than the configuration states would give, for PERF.md's second reading.
    `scan_dtype` rounds the recurrence's decay alone to that type (and back): a planted fault for the tests."""
    import jax
    import jax.numpy as jnp

    f = jnp.dtype(dtype or jnp.float32)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    batch, seq = inputs.shape
    d, eps = c["hidden_size"], c["rms_norm_eps"]
    n_head, n_kv, head_dim = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    group = n_head // n_kv
    lin_heads, dk, _ = _linear(c)
    beta_scale = 2.0 if c["kda_allow_neg_eigval"] else 1.0
    k = c["num_experts_per_tok"]
    held, first = c["n_routed_experts"], c.get("first_expert_held", 0)
    mask = jnp.tril(jnp.ones((seq, seq), bool))

    @jax.checkpoint
    def heads_of_one_kv(q, kk, v):
        """q (batch, group, seq, head_dim) against one key/value head (batch, seq, head_dim)."""
        scores = jnp.einsum("bgqh,bkh->bgqk", q, kk) / jnp.sqrt(jnp.asarray(head_dim, f))
        scores = jnp.where(mask, scores, -jnp.inf)
        return jnp.einsum("bgqk,bkh->bgqh", jax.nn.softmax(scores, axis=-1), v)

    def gqa(n, layer):
        q = (n @ layer["wq"].reshape(d, n_head * head_dim)).reshape(batch, seq, n_kv, group, head_dim)
        kk = (n @ layer["wk"].reshape(d, n_kv * head_dim)).reshape(batch, seq, n_kv, head_dim)
        v = (n @ layer["wv"].reshape(d, n_kv * head_dim)).reshape(batch, seq, n_kv, head_dim)
        out = jax.lax.map(lambda xs: heads_of_one_kv(*xs),
                          (q.transpose(2, 0, 3, 1, 4), kk.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)))
        out = out.transpose(1, 3, 0, 2, 4).reshape(batch, seq, n_head * head_dim)
        gate = jax.nn.sigmoid(n @ layer["w_gate"].reshape(d, n_head * head_dim))
        return (gate * out) @ layer["wo"].reshape(n_head * head_dim, d), None

    def kda(n, layer):
        def conv(w, taps):
            z = jax.nn.silu(causal_conv(n @ layer[w], layer[taps]))
            return z.reshape(batch, seq, lin_heads, dk).transpose(0, 2, 1, 3)

        q, kk, v = conv("wq", "conv_q"), conv("wk", "conv_k"), conv("wv", "conv_v")
        unit = lambda z: z / jnp.sqrt((z * z).sum(-1, keepdims=True) + jnp.asarray(1e-6, f))  # noqa: E731
        q, kk = unit(q) / jnp.sqrt(jnp.asarray(dk, f)), unit(kk)
        beta = beta_scale * jax.nn.sigmoid(n @ layer["w_b"]).transpose(0, 2, 1)  # (batch, heads, seq)
        g = jax.nn.softplus((n @ layer["w_f_down"]) @ layer["w_f_up"] + layer["dt_bias"])
        g = -jnp.exp(layer["A_log"])[None, :, None, None] * g.reshape(batch, seq, lin_heads, dk).transpose(0, 2, 1, 3)
        if scan_dtype is not None:
            g = g.astype(scan_dtype).astype(f)
        o = jax.vmap(jax.vmap(kda_recurrence))(q, kk, v, g, beta)  # (batch, heads, seq, dv)
        o = rms_norm(o, layer["o_norm"], eps).transpose(0, 2, 1, 3).reshape(batch, seq, lin_heads * dk)
        gate = jax.nn.sigmoid((n @ layer["w_g_down"]) @ layer["w_g_up"] + layer["b_g"])
        return (gate * o) @ layer["wo"], ((beta > 1).mean(), jnp.exp(g).min())

    @jax.checkpoint
    def expert(h, weight, w_gate, w_up, w_down):
        return weight[:, None] * ((jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down)

    def experts(n, moe):
        h = n.reshape(batch * seq, d)
        scores = jax.nn.sigmoid(h @ moe["router_w"])
        weights, chosen = routing_matrix(scores, k, c["norm_topk_prob"], c["routed_scaling_factor"])

        def add_expert(y, xs):
            weight, w_gate, w_up, w_down = xs
            return y + expert(h, weight, w_gate, w_up, w_down), None

        y, _ = jax.lax.scan(add_expert, jnp.zeros_like(h),
                            (weights.T[first:first + held], moe["w_gate"], moe["w_up"], moe["w_down"]))
        y = y + (jax.nn.silu(h @ moe["shared_gate"]) * (h @ moe["shared_up"])) @ moe["shared_down"]
        return y.reshape(batch, seq, d), chosen

    def block(kind):
        @jax.checkpoint
        def apply(x, layer):
            layer = jax.tree.map(lambda p: p.astype(f), layer)
            mixed, stats = (kda if kind == KDA else gqa)(rms_norm(x, layer["mixer_norm"], eps), layer)
            x = x + mixed
            y, chosen = experts(rms_norm(x, layer["moe_norm"], eps), layer["moe"])
            return x + y, (stats, chosen)
        return apply

    @jax.checkpoint
    def head_block(head, xs):
        x, t = xs  # (batch, positions, d), (batch, positions)
        logp = jax.nn.log_softmax(x @ head.T, axis=-1)
        return -jnp.take_along_axis(logp, t[..., None], axis=-1).sum()

    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(f)[inputs]
        share, least, chosen = [], [], []
        for kind, layer in layers_in_order(params["blocks"], c):
            x, (stats, of_layer) = block(kind)(x, layer)
            chosen.append(of_layer)
            if stats is not None:
                share.append(stats[0])
                least.append(stats[1])
        x = rms_norm(x, params["final_norm"].astype(f), eps)
        n = HEAD_BLOCKS if seq % HEAD_BLOCKS == 0 else 1
        by_block = lambda z: jnp.moveaxis(z.reshape(batch, n, seq // n, *z.shape[2:]), 1, 0)  # noqa: E731
        head = params["head"].astype(f)
        total = jax.lax.map(lambda xs: head_block(head, xs), (by_block(x), by_block(targets))).sum()
        loss = (total / (batch * seq)).astype(jnp.float32)
        return loss, {"chosen": jnp.stack(chosen),
                      "neg_eigval_share": jnp.stack(share).mean().astype(jnp.float32),
                      "decay_min": jnp.stack(least).min().astype(jnp.float32)}


# Tolerances of the agreement between the system (bf16 activations and matmul operands, the flash kernels and the
# scan's kernels in chunks of 128 with an f32 state and an f32 decay; f32 gates, router, norms, logits and
# parameters) and the reference (f32 throughout, the recurrence token by token, every held expert on every
# token), at seeded initial weights, on the one row (4,096 tokens) of the run's first batch that the harness
# hands `check`: the timed shape. Measured on the chip at the published widths under the cell's own traffic
# (`tools/solar_open2_readings.py`, PR 59, PERF.md section 6): 13 readings of the system, each its own seed
# (797891267 and 7978 among them); 2 of the reference itself with parameters, state, decay, norms, router and
# logits in bf16, the nearest precision below the configuration's ("below"); 2 of the system with the running
# log-decay rounded to bf16 where a chunk reads it ("decay bf16"), 2 with the state a chunk starts from rounded
# to bf16, 2 with every channel of a head at one decay (the scalar rule in this one's place):
#   loss            system off by 4.8e-6..2.5e-4 (a loss of 10.93 over 24,576 words); below 1.01e-2, 1.42e-2: the
#                   limit is eight times the system's largest reading and a fifth of below's smallest
#   gradient norm   system 2.5e-5..3.9e-5; below 3.3e-4, 4.9e-4; the scalar rule 6.2e-3: three times the largest
#                   reading, under two fifths of below's smallest
#   flipped choices system 0.59..0.66 % of the 131,072 (token, slot) choices of the four routers; below 2.42, 2.44 %:
#                   twice the largest reading, a little over half of below's
# The gradient at a leaf, the distance `|system - reference|` over `|reference|` (not a difference of norms: a
# leaf whose gradient points elsewhere at the right length is told). Of the first linear layer what only the
# scan's backward pass and the two gates reach; of the first attention layer its output gate; the first router:
#   w_f_down, w_f_up  the decay's low-rank projection: system 0.0135..0.0142 on every seed (the bf16 activations
#                   it multiplies); decay bf16 0.058..0.076; below 0.027..0.034: the limit that tells a decay kept
#                   in bf16 inside the scan, half again the largest reading and under four fifths of below's smallest
#   dt_bias         system 0.012..0.014; decay bf16 0.028, 0.033; below 0.052, 0.104: as above
#   w_b             system 0.013..0.014; below 0.033, 0.062
#   w_g_down, w_g_up  the output gate's: system 0.011..0.012; below 0.025..0.042
#   w_gate          the attention's gate: system 0.0131..0.0133; below 0.021, 0.023: 1.35 times the system's and four
#                   fifths of below's, the closest pair; the system's reading does not move with the seed
#   A_log           8 numbers, each a sum of 4,096 x 128 signed terms g dg: system 0.007..0.028 by the seed; below
#                   0.038, 0.097; decay bf16 0.025, 0.056; the scalar rule 0.95, 1.35. It cannot tell the precision:
#                   twice the largest reading, for another function
#   router_w        follows the flipped choices (a flipped pair moves a whole row of the router's gradient): system
#                   0.094..0.212, below 0.28, 0.35. It cannot tell the precision on every seed: the limit is there
#                   for another function (scores that sum to one, a weight left unnormalised: 1.0 and more)
# What none of them tells at these widths is the state alone in bf16 (rounded where a chunk hands it on: every
# reading inside the system's own range, the loss 2.3e-5): 2^-9 of a state is what every bf16 activation round it
# already carries, as in the Olmo-Hybrid cell. That fault is held where it can be told, in float32 on the CPU
# (`tests/test_kda.py`: forty times that test's limit), and PERF.md section 7 has the row. No comparison of losses
# can see parameters kept in bf16: the parameters' and the optimizer moments' dtype is checked by name.
LOSS_ABS_TOL = 2e-3
GRAD_NORM_REL_TOL = 1.2e-4
FLIPPED_SHARE_TOL = 1.3e-2
LEAF_GRAD_REL_TOL = {"w_f_down": 0.021, "w_f_up": 0.021, "A_log": 0.06, "dt_bias": 0.021, "w_b": 0.021,
                     "w_g_down": 0.018, "w_g_up": 0.018, "w_gate": 0.018, "router_w": 0.5}
KDA_LEAVES = ("w_f_down", "w_f_up", "A_log", "dt_bias", "w_b", "w_g_down", "w_g_up")
CHECKED_LEAVES = KDA_LEAVES + ("w_gate", "router_w")


def _checked(grads):
    """The gradient at each of `CHECKED_LEAVES`, f32: the first period's first linear layer's, the first
    attention layer's gate and the first layer's router."""
    import jax.numpy as jnp

    gqa, first_kda = grads["blocks"]["period"][0], grads["blocks"]["period"][1]
    leaves = [first_kda[name][0] for name in KDA_LEAVES] + [gqa["w_gate"][0], gqa["moe"]["router_w"][0]]
    return [leaf.astype(jnp.float32) for leaf in leaves]


def losses_and_grads(system: System, dtype=None, scan_dtype=None):
    """(of_system, of_reference): each `(params, tokens) -> (loss, the gradient's global norm, its checked
    leaves[, more])`, a program each so that the two gradient trees (3.4 GB each at the published widths) are
    never held at once. The system's `more` is its `routing_stats`, the reference's its statistics."""
    import jax
    import optax

    from ray_tpu.models import solar_open2

    cfg, mesh, c = system.cfg, system.mesh, system.c

    def of_system(params, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: solar_open2.loss_fn(p, {"tokens": tokens}, cfg, mesh=mesh))(params)
        return loss, optax.global_norm(grads), _checked(grads), solar_open2.routing_stats(params, tokens[:, :-1], cfg)

    def of_reference(params, tokens):
        (loss, stats), grads = jax.value_and_grad(
            lambda p: reference_loss(p, tokens, c, dtype, scan_dtype), has_aux=True)(params)
        return loss, optax.global_norm(grads), _checked(grads), stats

    return of_system, of_reference


def _issued_rows(held_sizes) -> int:
    """Rows of products the grouped-matmul kernels issue for these groups, a layer's three products forward
    and for both gradients."""
    from ray_tpu.ops import grouped_matmul as gm

    return sum(3 * (2 * gm.issued_rows(sizes, gm.SUB_ROWS) + gm.issued_rows(sizes, gm.DRHS_SUB_ROWS))
               for sizes in held_sizes)


def check(system: System, tokens, *, program=None, reference=None) -> Dict[str, Any]:
    """Loss, global gradient norm, the gradient at nine leaves (`CHECKED_LEAVES`) and the experts chosen, of
    the system's `loss_fn` (through the flash kernels, the scan's kernels and the held-experts layer) against
    the reference's, on `tokens` (a jax array, already placed) with the run's own parameters; and what the
    routers did (`routing_stats`: `dropped` must be 0). A limit is the configuration's own (`check_tolerances`:
    the rehearsal's toy) where it gives one, else this file's. `program`, `(params, tokens) -> (loss, the
    gradient's norm, its checked leaves, routing_stats)`, stands in the system's place
    (`tools/solar_open2_readings.py`: the reference a precision below, the system under a planted fault), and
    `reference` is what the reference's program gave for these tokens where the caller has run it already."""
    import jax
    import jax.numpy as jnp

    c = system.c
    own = c.get("check_tolerances", {})
    loss_tol = own.get("loss_abs", LOSS_ABS_TOL)
    grad_tol = own.get("grad_norm_rel", GRAD_NORM_REL_TOL)
    flipped_tol = own.get("flipped_share", FLIPPED_SHARE_TOL)
    leaf_tol = own.get("leaf_grad_rel", LEAF_GRAD_REL_TOL)
    if not isinstance(leaf_tol, dict):
        leaf_tol = dict.fromkeys(CHECKED_LEAVES, leaf_tol)
    params = system.state.params
    want_dtype = jnp.dtype(c["param_dtype"])
    leaves = jax.tree.leaves(params) + [
        x for x in jax.tree.leaves(system.state.opt_state) if getattr(x, "ndim", 0) > 0]
    wrong_dtype = sorted({str(x.dtype) for x in leaves if x.dtype != want_dtype})
    del leaves
    of_system, of_reference = losses_and_grads(system)
    with _moments_set_aside(system):
        sys_loss, sys_norm, sys_leaves, stats = jax.jit(program or of_system)(params, tokens)
        if reference is None:
            reference = jax.jit(of_reference)(params, tokens)
        ref_loss, ref_norm, ref_leaves, ref_stats = reference
        # experts (layers, tokens, k): is each of the system's choices one of the reference's?
        flipped = 1.0 - jnp.take_along_axis(ref_stats["chosen"], stats.pop("experts"), axis=-1).mean()
        norm = lambda x: float(jnp.sqrt(jnp.sum(jnp.square(x))))  # noqa: E731
        leaf_err = {name: norm(a - b) / max(norm(b), 1e-30)
                    for name, a, b in zip(CHECKED_LEAVES, sys_leaves, ref_leaves)}
        leaf_ref = {name: norm(b) for name, b in zip(CHECKED_LEAVES, ref_leaves)}
        del sys_leaves, ref_leaves, reference
    got = [float(x) for x in (sys_loss, sys_norm, ref_loss, ref_norm)]
    sys_loss, sys_norm, ref_loss, ref_norm = got
    stats = jax.device_get(stats)
    per_expert = stats["tokens_per_expert"]
    held, elsewhere = int(stats["held_pairs"].sum()), int(stats["elsewhere_pairs"].sum())
    first = c.get("first_expert_held", 0)
    held_sizes = [[int(x) for x in layer[first:first + c["n_routed_experts"]]] for layer in per_expert]
    out = {
        "loss_system": sys_loss, "loss_reference": ref_loss,
        "grad_norm_system": sys_norm, "grad_norm_reference": ref_norm,
        "loss_abs_err": abs(sys_loss - ref_loss),
        "grad_norm_rel_err": abs(sys_norm - ref_norm) / max(ref_norm, 1e-30),
        "leaf_grad_rel_err": leaf_err,
        "leaf_grad_norm_reference": leaf_ref,
        "expert_choices_flipped_share": float(flipped),
        "kda.neg_eigval_share": float(ref_stats["neg_eigval_share"]),
        "kda.decay_min": float(ref_stats["decay_min"]),
        "state_dtypes_other_than_stated": wrong_dtype,
        "routing": {
            "pairs_per_layer": int(per_expert[0].sum()),
            "held_pairs": held,
            "elsewhere_pairs": elsewhere,
            "held_pairs_share": held / max(held + elsewhere, 1),
            "held_pairs_per_layer": [int(x) for x in stats["held_pairs"]],
            "held_tokens_per_expert": held_sizes,
            "issued_over_held": _issued_rows(held_sizes) / max(9 * held, 1),
            "dropped": int(stats["dropped"].sum()),
            "compact_layers": int(stats["compact"].sum()),
            "load_max_over_mean": float(stats["load_max_over_mean"].max()),
            "tokens_per_expert_min": int(per_expert.min()),
            "tokens_per_expert_max": int(per_expert.max()),
        },
        "limits": {"loss_abs_err": loss_tol, "grad_norm_rel_err": grad_tol, "leaf_grad_rel_err": leaf_tol,
                   "expert_choices_flipped_share": flipped_tol},
    }
    out["ok"] = bool(
        all(map(math.isfinite, got + list(leaf_err.values()))) and out["loss_abs_err"] <= loss_tol
        and out["grad_norm_rel_err"] <= grad_tol and not wrong_dtype
        and out["routing"]["dropped"] == 0 and out["expert_choices_flipped_share"] <= flipped_tol
        and all(err <= leaf_tol[name] for name, err in leaf_err.items()))
    return out
