"""Xing4.0 (Xing4.0-29B-A4B) for the benchmark: the system under test built through ray_tpu's public API, a plain
float32 reference written from the issue's equations, the comparison that decides `correct`, and the arithmetic of
FLOPs and bytes.

A configuration file (`benchmark/configs/<name>.json`) with `"model": "xing4"` is served by this module. Keys read,
under the names of the source's `config.json`: those `benchmark/models/glm4_moe_lite.py` reads (the block is that
family's: latent attention, a leading dense SwiGLU, held routed experts beside a shared one), `rope_scaling` (YaRN's
`factor`, `original_max_position_embeddings`, `beta_fast`, `beta_slow`, `mscale`, `mscale_all_dim`), `hc_mult`,
`hc_sinkhorn_iters`, `hc_eps`, `mhc_h_res_clamp_min`, `mhc_h_res_clamp_max`; and the benchmark's own:
`first_expert_held`, `hc_phi_std`, `dtype`, `param_dtype`, `remat_policy`, `attention`, `learning_rate` (the peak),
`warmup_steps` and `total_steps`.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

from benchmark.models import glm4_moe_lite as glm
from benchmark.models.glm4_moe_lite import (  # noqa: F401  (the same family's counts, routing and layer order)
    attention_matmul_params, head_dim, held_pairs_per_layer, layers_in_order, moe_expert_bytes_per_step,
    moe_expert_flops_per_step, router_width)
from benchmark.models.lfm2 import _issued_rows, rms_norm, routing_matrix
from benchmark.models.olmo_hybrid import _moments_set_aside  # AdamW's zero moments out of the check's way: 6.1 GB here

# ------------------------------------------------------------------ arithmetic
# No jax below this line until `build`: the parent and the tests use these.
# Everything counts what this chip computes: the routed experts it holds, the
# shared expert whole, the slice of the vocabulary it holds, the layers it holds.


def sublayers(c: Dict[str, Any]) -> int:
    """The sublayers the streams are mixed round: attention and the feed-forward of every layer."""
    return 2 * c["num_hidden_layers"]


def phi_entries(c: Dict[str, Any]) -> int:
    """One sublayer's Phi: (hc_mult x hidden_size) x (hc_mult^2 + 2 hc_mult)."""
    n = c["hc_mult"]
    return n * c["hidden_size"] * (n * n + 2 * n)


def num_params(c: Dict[str, Any]) -> int:
    """`glm4_moe_lite.num_params` (the family's layers, by hand there) and for every sublayer its Phi, the three
    scales and the hc_mult^2 + 2 hc_mult biases."""
    n = c["hc_mult"]
    return glm.num_params(c) + sublayers(c) * (phi_entries(c) + 3 + n * n + 2 * n)


def active_matmul_params(c: Dict[str, Any]) -> float:
    """`glm4_moe_lite.active_matmul_params` and every sublayer's Phi, which every token's streams multiply."""
    return glm.active_matmul_params(c) + sublayers(c) * phi_entries(c)


def train_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    """6 per active matmul parameter, plus attention over the full square of `seq` positions, q . k at the keys' 192
    and p . v at the values' 128 (6 * heads * (192 + 128) * seq a call: `gpt2.train_flops_per_token`'s convention
    where the two are one). The mixes and the Sinkhorn rounds multiply no matrix. Recomputation is not counted."""
    widths = head_dim(c) + c["v_head_dim"]
    return 6.0 * active_matmul_params(c) + 6.0 * c["num_hidden_layers"] * c["num_attention_heads"] * widths * seq


def flash_flops_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """FLOPs the causal attention of a step requires at the published widths, per (row, head) over the causal half:
    forward q . k (192) and p . v (128); backward dv and dp (128), dq and dk (192): 2 * seq^2 * width each. The
    kernel's recomputation of the scores is not counted, nor a column it pads."""
    per_head = 2 * seq * seq * 3 * (head_dim(c) + c["v_head_dim"]) / 2
    return per_head * rows * c["num_attention_heads"] * c["num_hidden_layers"]


def flash_bytes_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """Bytes the two kernels must move in bf16: forward reads q, k (192) and v (128) and writes o (128) and the row
    statistics (f32); backward reads q, k, v, do, the statistics and delta and writes dq, dk, dv."""
    key, value, stat = seq * head_dim(c) * 2, seq * c["v_head_dim"] * 2, seq * 4
    per_head = (2 * key + 2 * value + stat) + (4 * key + 3 * value + 2 * stat)
    return per_head * rows * c["num_attention_heads"] * c["num_hidden_layers"]


def mhc_mix_bytes_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """Bytes any implementation of the stream mixing must move in a step, forward and backward, the streams in
    `dtype`'s 2 B: forward a sublayer reads its streams once (the maps and u from one pass) and u is written; y is
    read, the streams read again and the new ones written. Backward the same arrays' gradients flow the other way and
    the streams are read for them: twice the forward's. No recomputation (the compiled step's is its own)."""
    stream = rows * seq * c["hidden_size"] * 2
    forward = (c["hc_mult"] + 1) * stream + (1 + 2 * c["hc_mult"]) * stream
    return 3.0 * forward * sublayers(c)


def mhc_bwd_bytes_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """Bytes the mixes' two backward kernels must move in a step, every operand read once and every result written once,
    the streams, y and their gradients in `dtype`'s 2 B, a token's map values and Phi in float32. `mhc_post_bwd` (all of
    `post_res_mix`'s gradient) reads the cotangent of X', X and y and writes dX and dy, 3 n + 2 planes, beside a token's
    n + n^2 values of H_post and H_res in and their gradients out; `mhc_pre_bwd` (the gradient of the maps' product and
    norm) reads X and writes dX, 2 n planes, beside a token's n^2 + 2 n columns of r dm and its norm's coefficient in,
    and Phi in and dPhi out once a call. Not `pre_mix`'s rule nor the sum of the streams' three cotangents, which are
    XLA's: of the 1.23 GB a sublayer's backward moves (PR 67) the kernels' part is 0.65 GB."""
    n, d = c["hc_mult"], c["hidden_size"]
    tokens, maps = rows * seq, n * n + 2 * n
    post = (3 * n + 2) * tokens * d * 2 + 2 * tokens * (n + n * n) * 4
    pre = 2 * n * tokens * d * 2 + tokens * (maps + 1) * 4 + 2 * maps * n * d * 4
    return float(post + pre) * sublayers(c)


# ---------------------------------------------------------------------- system
def xing4_config(c: Dict[str, Any]):
    import jax.numpy as jnp

    from ray_tpu.models.llama import Yarn
    from ray_tpu.models.xing4 import Xing4Config

    assert c["topk_method"] == "noaux_tc" and c["n_group"] == c["topk_group"] == 1, "the only routing written"
    assert c["scoring_func"] == "sigmoid" and not c["attention_bias"] and not c["tie_word_embeddings"]
    assert c["hidden_act"] == "silu" and c["num_key_value_heads"] == c["num_attention_heads"]
    assert c["num_nextn_predict_layers"] == 0, "the prediction module is the pipeline's last stage's"
    scaling = c["rope_scaling"]
    assert scaling["type"] == "yarn"
    return Xing4Config(
        vocab_size=c["vocab_size"], n_layer=c["num_hidden_layers"],
        n_dense_layers=c["first_k_dense_replace"], n_head=c["num_attention_heads"],
        d_model=c["hidden_size"], q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"], qk_rope_head_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"], d_ff=c["intermediate_size"], d_expert=c["moe_intermediate_size"],
        n_experts=router_width(c), experts_per_token=c["num_experts_per_tok"],
        n_shared_experts=c["n_shared_experts"], n_experts_held=c["n_routed_experts"],
        first_expert_held=c.get("first_expert_held", 0), norm_topk_prob=c["norm_topk_prob"],
        routed_scaling_factor=float(c["routed_scaling_factor"]), n_predict_layers=0,
        max_seq_len=c["max_position_embeddings"], rope_theta=float(c["rope_theta"]),
        yarn=Yarn(factor=float(scaling["factor"]),
                  original_max_position_embeddings=scaling["original_max_position_embeddings"],
                  beta_fast=float(scaling["beta_fast"]), beta_slow=float(scaling["beta_slow"]),
                  mscale=float(scaling["mscale"]), mscale_all_dim=float(scaling["mscale_all_dim"])),
        hc_mult=c["hc_mult"], hc_sinkhorn_iters=c["hc_sinkhorn_iters"], hc_eps=float(c["hc_eps"]),
        hc_clamp=(float(c["mhc_h_res_clamp_min"]), float(c["mhc_h_res_clamp_max"])),
        hc_phi_std=float(c["hc_phi_std"]),
        norm_eps=c["rms_norm_eps"], dtype=jnp.dtype(c["dtype"]), param_dtype=jnp.dtype(c["param_dtype"]),
        remat_policy=c["remat_policy"], attention=c["attention"],
    )


class System:
    """cfg, optimizer, state and jitted step, made as a user makes them."""

    def __init__(self, c: Dict[str, Any], mesh, seed: int):
        import jax

        from ray_tpu.models import create_train_state, default_optimizer, make_train_step

        self.c = c
        self.mesh = mesh
        self.cfg = xing4_config(c)
        self.optimizer = default_optimizer(
            learning_rate=c["learning_rate"], warmup_steps=c.get("warmup_steps", 0),
            total_steps=c.get("total_steps", 0))
        self.state = create_train_state(self.cfg, jax.random.PRNGKey(seed), self.optimizer, mesh=mesh)
        self.step = make_train_step(self.cfg, self.optimizer, mesh=mesh)

    def attention_path(self, rows_per_device: int, seq: int, platform: str) -> str:
        from ray_tpu.ops.flash_attention import select_backend

        return select_backend((rows_per_device, self.cfg.n_head, seq, self.cfg.head_dim), platform)


def build(c: Dict[str, Any], mesh, seed: int) -> System:
    return System(c, mesh, seed)


# ------------------------------------------------------------------- reference
HEAD_ROWS = 2048  # positions whose f32 logits are held at once


def yarn_tables(c: Dict[str, Any], seq: int):
    """(cos, sin, the softmax's scale) of YaRN as DeepSeek-V3's modelling code writes it, in numpy float64: the pair
    i of the `qk_rope_head_dim` rotary columns turns at `theta^(-2i/dim)` (extrapolation) below the pair that turns
    `beta_fast` times over the original context, at that over `factor` (interpolation) above the pair that turns
    `beta_slow` times, and at a linear blend between; both tables times `mscale(factor, mscale) / mscale(factor,
    mscale_all_dim)`, `mscale(s, m) = 0.1 m ln s + 1`; the scores times `head_dim^-1/2 mscale(factor,
    mscale_all_dim)^2`. The tables are (seq, dim): both halves the same angles (`rotate_half`)."""
    import numpy as np

    s, dim, base = c["rope_scaling"], c["qk_rope_head_dim"], float(c["rope_theta"])
    factor, original = float(s["factor"]), s["original_max_position_embeddings"]
    extrapolated = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    interpolated = extrapolated / factor

    def pair_that_turns(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(pair_that_turns(s["beta_fast"])), 0)
    high = min(math.ceil(pair_that_turns(s["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / ((high + 0.001 if high == low else high) - low), 0, 1)
    inv_freq = interpolated * ramp + extrapolated * (1 - ramp)

    def mscale(m):
        return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0

    angles = np.arange(seq, dtype=np.float64)[:, None] * inv_freq[None]
    angles = np.concatenate([angles, angles], axis=-1)
    tables = mscale(s["mscale"]) / mscale(s["mscale_all_dim"])
    return np.cos(angles) * tables, np.sin(angles) * tables, head_dim(c) ** -0.5 * mscale(s["mscale_all_dim"]) ** 2


def stream_maps(x, hc, c: Dict[str, Any], *, dynamic: bool = True, static: bool = True,
                rounds: Optional[int] = None, clamp: bool = True):
    """(H_pre (batch, seq, n), H_post (batch, seq, n), H_res (batch, seq, n, n)) of the streams x (batch, seq, n, d),
    per token, as the issue writes them: `r = (mean(vec(X)^2) + rms_norm_eps)^-1/2`, `m = r (vec(X) Phi)`, `H_pre =
    sigmoid(a_pre m[:n] + b_pre)`, `H_post = 2 sigmoid(a_post m[n:2n] + b_post)`, `M = exp(clip(a_res mat(m[2n:]) +
    B_res, min, max))`, then `hc_sinkhorn_iters` times the rows of M over (their sum + `hc_eps`) and the columns of
    M over (their sum + `hc_eps`). The trained leaf holds Phi's columns as rows, (n^2 + 2 n, n, d). Planted faults:
    `dynamic` False leaves a_* m out, `static` False the biases, `rounds` puts another count in 20's place, `clamp`
    False leaves the clip out."""
    import jax
    import jax.numpy as jnp

    n = c["hc_mult"]
    flat = x.reshape(*x.shape[:2], -1)
    r = 1.0 / jnp.sqrt((flat * flat).mean(-1, keepdims=True) + c["rms_norm_eps"])
    m = r * (flat @ hc["phi"].reshape(n * n + 2 * n, -1).T)
    a_pre, a_post, a_res = hc["alpha"] if dynamic else jnp.zeros_like(hc["alpha"])
    bias = hc["bias"] if static else jnp.zeros_like(hc["bias"])
    h_pre = jax.nn.sigmoid(a_pre * m[..., :n] + bias[:n])
    h_post = 2.0 * jax.nn.sigmoid(a_post * m[..., n:2 * n] + bias[n:2 * n])
    logits = a_res * m[..., 2 * n:].reshape(*m.shape[:2], n, n) + bias[2 * n:].reshape(n, n)
    if clamp:
        logits = jnp.clip(logits, c["mhc_h_res_clamp_min"], c["mhc_h_res_clamp_max"])
    h_res = jnp.exp(logits)
    for _ in range(c["hc_sinkhorn_iters"] if rounds is None else rounds):
        h_res = h_res / (h_res.sum(-1, keepdims=True) + c["hc_eps"])  # a row's entries over the row's sum
        h_res = h_res / (h_res.sum(-2, keepdims=True) + c["hc_eps"])  # a column's over the column's
    return h_pre, h_post, h_res


def reference_loss(params, tokens, c: Dict[str, Any], dtype=None, **faults):
    """Xing4.0's next-token objective (the equations of ISSUE 66; DeepSeek-V3's for what they leave; what the
    source's `config.json` does not give is under the configuration's `assumed`) in float32 `jax.numpy` on `tokens`
    (batch, seq + 1); returns (loss, {"chosen": (expert layers, tokens, experts) bool, the experts each token was
    given among all the router scores; "res_sum_err": the largest distance from 1 of a row or column sum of any
    sublayer's H_res}).

    The streams X (batch, seq, n, d) start as n copies of the token's embedding. Round each sublayer F of each
    layer, attention and then the feed-forward: `(H_pre, H_post, H_res) = stream_maps(X)`; `u = sum_i H_pre[i]
    X[i]`; `y = F(N(u))`, N an RMSNorm with a scale; `X'[i] = H_post[i] y + sum_j H_res[i, j] X[j]`. Attention:
    `c_q = N(h W_qa)`, `q = c_q W_qb` in heads of (128 | 64); `[c_kv | k_r] = h W_kva`, `c_kv = N(c_kv)`, `[k_n | v]
    = c_kv W_kvb` in heads of (128 | 128); the 64 rotated on halves by `yarn_tables`, the one `k_r` shared by every
    head; softmax over the keys j <= i of query i (a comparison of positions) at `yarn_tables`' scale; `W_o` on the
    heads' 128. Feed-forward of the first `first_k_dense_replace` layers `W2 (silu(W1 n) * W3 n)`; of the others `s
    = sigmoid(W_r n)`, the `num_experts_per_tok` largest of `s + expert_bias`, weights `s` at the chosen over their
    sum times `routed_scaling_factor`, `sum_e w_e E_e(n)` over the experts this chip holds plus the shared expert
    whole: the partial sum goes on, as in the system. Out: the streams' sum, a final RMSNorm, the head (untied), the
    mean cross entropy of the next token. No kernel, no sort, no grouped matmul, no bf16.

    Departures from a line-by-line transcription, none changes the arithmetic: each layer, each head, each expert
    and each chunk of the head's logits is made again in the backward pass (`jax.checkpoint`); the renormalisation
    divides by the plain sum (DeepSeek-V3's code adds 1e-20).

    `dtype` (default float32) computes everything, parameters, maps, norms, rotation, router and logits included,
    in that type instead (the cross entropy of those logits in float32): what a lower precision than the
    configuration states would give, for PERF.md's second reading. `faults` are `stream_maps`' planted ones."""
    import jax
    import jax.numpy as jnp

    f = jnp.dtype(dtype or jnp.float32)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    batch, seq = inputs.shape
    d, eps, k, n = c["hidden_size"], c["rms_norm_eps"], c["num_experts_per_tok"], c["hc_mult"]
    nh, nope, rope, kvl = c["num_attention_heads"], c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["kv_lora_rank"]
    held, first = c["n_routed_experts"], c.get("first_expert_held", 0)
    cos, sin, scale = yarn_tables(c, seq)
    cos, sin = jnp.asarray(cos, f), jnp.asarray(sin, f)
    causal = jnp.arange(seq)[:, None] >= jnp.arange(seq)[None, :]  # key j is seen by query i where j <= i

    def rotated(x):  # (..., seq, rope)
        return x * cos + jnp.concatenate([-x[..., rope // 2:], x[..., :rope // 2]], axis=-1) * sin

    def attention(h, layer):
        """h (batch, seq, d) normed -> W_o on the heads' output, (batch, seq, d)."""
        c_q = rms_norm(h @ layer["wq_a"], layer["q_a_norm"], eps)
        q = jnp.einsum("bsr,rnh->nbsh", c_q, layer["wq_b"])  # (heads, batch, seq, 128 + 64)
        kv = h @ layer["wkv_a"]
        c_kv, k_r = rms_norm(kv[..., :kvl], layer["kv_a_norm"], eps), rotated(kv[..., kvl:])
        k_n_v = jnp.einsum("bsr,rnh->nbsh", c_kv, layer["wkv_b"])  # (heads, batch, seq, 128 + 128)

        @jax.checkpoint
        def one_head(q, k_n_v):
            q = jnp.concatenate([q[..., :nope], rotated(q[..., nope:])], axis=-1)
            key = jnp.concatenate([k_n_v[..., :nope], k_r], axis=-1)
            scores = jnp.einsum("bqh,bkh->bqk", q, key) * jnp.asarray(scale, f)
            return jnp.einsum("bqk,bkh->bqh", jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1),
                              k_n_v[..., nope:])

        o = jax.lax.map(lambda xs: one_head(*xs), (q, k_n_v))  # (heads, batch, seq, 128)
        return o.transpose(1, 2, 0, 3).reshape(batch, seq, -1) @ layer["wo"].reshape(-1, d)

    def swiglu(h, w_gate, w_up, w_down):
        return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down

    @jax.checkpoint
    def expert(h, weight, w_gate, w_up, w_down):
        return weight[:, None] * swiglu(h, w_gate, w_up, w_down)

    def experts(h, moe):
        h = h.reshape(batch * seq, d)
        scores = jax.nn.sigmoid(h @ moe["router_w"])
        weights, chosen = routing_matrix(scores, moe["expert_bias"], k, c["norm_topk_prob"],
                                         jnp.asarray(c["routed_scaling_factor"], f))

        def add_expert(y, xs):
            return y + expert(h, *xs), None

        y, _ = jax.lax.scan(add_expert, jnp.zeros_like(h),
                            (weights.T[first:first + held], moe["w_gate"], moe["w_up"], moe["w_down"]))
        y = y + swiglu(h, moe["shared_gate"], moe["shared_up"], moe["shared_down"])
        return y.reshape(batch, seq, d), chosen

    def round_sublayer(x, hc, sublayer):
        """x (batch, seq, n, d) -> (X', what the sublayer gave beside y, H_res's largest row or column sum's
        distance from 1)."""
        h_pre, h_post, h_res = stream_maps(x, hc, c, **faults)
        y, more = sublayer(jnp.einsum("bsn,bsnd->bsd", h_pre, x))
        x = h_post[..., None] * y[:, :, None, :] + jnp.einsum("bsij,bsjd->bsid", h_res, x)
        err = jnp.maximum(jnp.abs(h_res.sum(-1) - 1).max(), jnp.abs(h_res.sum(-2) - 1).max())
        return x, more, err

    @jax.checkpoint
    def block(x, layer):
        layer = jax.tree.map(lambda p: p.astype(f), layer)
        x, _, err_a = round_sublayer(
            x, layer["hc_attn"], lambda u: (attention(rms_norm(u, layer["attn_norm"], eps), layer), None))

        def feed_forward(u):
            h = rms_norm(u, layer["ffn_norm"], eps)
            if "moe" not in layer:
                return swiglu(h, layer["w_gate"], layer["w_up"], layer["w_down"]), None
            return experts(h, layer["moe"])

        x, chosen, err_f = round_sublayer(x, layer["hc_ffn"], feed_forward)
        return x, chosen, jnp.maximum(err_a, err_f).astype(jnp.float32)

    head_rows = math.gcd(seq, HEAD_ROWS)

    @jax.checkpoint
    def head_chunk(table, xs):
        x, t = xs  # (batch, head_rows, d), (batch, head_rows)
        log_p = jax.nn.log_softmax((x @ table.T).astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(log_p, t[..., None], axis=-1).sum()

    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(f)[inputs]
        x = jnp.broadcast_to(x[:, :, None, :], (batch, seq, n, d))
        chosen, errs = [], []
        for layer in layers_in_order(params, c):
            x, of_layer, err = block(x, layer)
            errs.append(err)
            if of_layer is not None:
                chosen.append(of_layer)
        x = rms_norm(x.sum(axis=2), params["final_norm"].astype(f), eps)
        chunks = lambda a: jnp.moveaxis(a.reshape(batch, seq // head_rows, head_rows, *a.shape[2:]), 1, 0)  # noqa: E731
        table = params["lm_head"].astype(f)
        total = jax.lax.map(lambda xs: head_chunk(table, xs), (chunks(x), chunks(targets))).sum()
    return (total / (batch * seq)).astype(jnp.float32), {"chosen": jnp.stack(chosen), "res_sum_err": jnp.stack(errs).max()}


# Tolerances of the agreement between the system (bf16 streams, activations and matmul operands, the flash kernels at
# keys of 192 and values of 128, grouped matmuls over the held groups; f32 maps, Sinkhorn rounds, mixes, router, norms,
# logits and parameters) and the reference (f32 throughout, a token's maps as n x n arrays, masks as comparisons of
# positions, every held expert on every token), at seeded initial weights, on the one row (4,096 tokens) of the run's
# first batch that the harness hands `check`: the timed shape. Set from readings on the chip at the published widths
# under the cell's own traffic (`tools/xing4_readings.py` and the cell's first traced run, PR 66, PERF.md section 6;
# every seed its own; set before the cell's twelve runs and not moved after them): the system (9 readings), and in
# the program's place the reference itself a precision below the stated one ("below", 3 readings: parameters, maps,
# norms, rotation, router and logits in bf16) or under a planted fault of the maps (the dynamic part left out, a_* =
# 0; the static part, every bias 0: one reading each; one Sinkhorn round in twenty's place: three).
#   loss            system 1.7e-4..8.2e-4; below 2.6e-4..5.5e-4 (it cannot tell the precision); a_* = 0 8.0e-3: 3.7
#                   times the system's largest, under half the fault's
#   gradient norm   system 6.7e-6..2.8e-4; below 7.6e-4..1.2e-3; one round 1.1e-3..3.2e-3; no biases 4.2e-2; a_* = 0
#                   5.6e-2: 2e-3 is seven times the system's largest and a twentieth of a map's part left out
#   flipped choices system 0.93..1.32 % of the 65,536 (token, slot) choices of the four routers (mean 1.10, deviation
#                   0.12); below 1.85, 1.96, 1.99; one round 3.6..4.8; a part left out 12..16: the limit that tells the
#                   precision, 1.6 %, four deviations above the system's mean, a fifth above its largest reading and a
#                   seventh under below's least
#   H_res's sums    system and reference alike 0.018..0.027 (what twenty rounds leave of the rows' sums at biases of
#                   N(0, 1); the columns' are 1e-6 off); nineteen rounds 0.026 (not told, and no other reading tells
#                   it: 4e-3 at the worst leaf); one round 1.36..1.57: 0.1 is 3.7 times the largest and a
#                   thirteenth of one round's
LOSS_ABS_TOL = 3e-3
GRAD_NORM_REL_TOL = 2e-3
FLIPPED_SHARE_TOL = 1.6e-2
RES_SUM_ERR_TOL = 0.1
# The gradient at a leaf, `|system - reference| / |reference|` (not a difference of norms: a leaf whose gradient
# points elsewhere at the right length is told): of the first expert layer both sublayers' Phi, `wkv_b` (keys and
# values of two widths from one matrix) and the router; and each map's scale and each map's biases over every
# sublayer of the five layers as one vector (a_pre: ten numbers; B_res: 160). Behind the turned choices every large
# leaf reads alike, Phi 0.051..0.078 and `wkv_b` 0.040..0.065 (below 0.075..0.094 and 0.067..0.082: no leaf tells the
# precision), the router 0.14..0.26 (below 0.19..0.29). What the leaves tell is a part of the maps left out: a_* = 0
# reads exactly 1 at both Phi and at every scale (the reference's gradient stands against none), `wkv_b` 0.56, the
# router 0.71; every bias 0 reads exactly 1 at the three groups of biases, Phi 0.59..0.70, `wkv_b` 0.38; one round
# reads Phi 0.98..0.99 (attention's) and 0.30..0.37 (the feed-forward's), B_res 2.0..2.7, a_res 1.7..10, `wkv_b`
# 0.15..0.19. A scale's gradient is ten sums of 16,384 terms that nearly cancel, and what the turned choices add is
# a large share of what is left in a seed whose sums cancel further: system a_pre 0.015..0.30, a_post 0.028..0.28,
# a_res 0.032..0.25 (below 0.055..0.71): their limit, 0.75, is 2.5 times the largest and three quarters of the 1 that
# a part left out reads, and Phi's, 0.2, is what holds the dynamic part tightly (2.6 times the system's largest, two
# thirds of one round's least). The biases' groups read 0.026..0.14: 0.5 is 3.5 times that and half of 1. `wkv_b`'s
# 0.13 is twice the system's largest and under every fault's; the router's 0.5 is there for another function.
MAP_LEAVES = ("a_pre", "a_post", "a_res", "b_pre", "b_post", "b_res")
CHECKED_LEAVES = ("hc_attn.phi", "hc_ffn.phi") + MAP_LEAVES + ("wkv_b", "router_w")
LEAF_GRAD_REL_TOL = {**dict.fromkeys(("hc_attn.phi", "hc_ffn.phi"), 0.2), **dict.fromkeys(MAP_LEAVES[:3], 0.75),
                     **dict.fromkeys(MAP_LEAVES[3:], 0.5), "wkv_b": 0.13, "router_w": 0.5}


def _checked(grads, c: Dict[str, Any]):
    """The gradient at each of `CHECKED_LEAVES`, f32, out of the tree the system trains."""
    import jax.numpy as jnp

    layers, n = layers_in_order(grads, c), c["hc_mult"]
    first = layers[c["first_k_dense_replace"]]  # the first expert layer
    maps = [layer[group] for layer in layers for group in ("hc_attn", "hc_ffn")]
    alpha, bias = jnp.stack([m["alpha"] for m in maps]), jnp.stack([m["bias"] for m in maps])  # (10, 3), (10, 24)
    leaves = [first["hc_attn"]["phi"], first["hc_ffn"]["phi"], alpha[:, 0], alpha[:, 1], alpha[:, 2],
              bias[:, :n], bias[:, n:2 * n], bias[:, 2 * n:], first["wkv_b"], first["moe"]["router_w"]]
    return [leaf.astype(jnp.float32) for leaf in leaves]


def losses_and_grads(system: System, dtype=None, cfg=None, **faults):
    """(of_system, of_reference): each `(params, tokens) -> (loss, the gradient's global norm, its checked leaves,
    more)`, a program each so that the two gradient trees (3.0 GB each at the published widths) are never held at
    once. The system's `more` is its `routing_stats`, the reference's its chosen experts and `res_sum_err`. `cfg`
    puts another configuration of the program in the system's place, `dtype` and `faults` are `reference_loss`'s."""
    import jax
    import optax

    from ray_tpu.models import xing4

    cfg, mesh, c = cfg or system.cfg, system.mesh, system.c

    def of_system(params, tokens):
        loss, grads = jax.value_and_grad(lambda p: xing4.loss_fn(p, {"tokens": tokens}, cfg, mesh=mesh))(params)
        return loss, optax.global_norm(grads), _checked(grads, c), xing4.routing_stats(params, tokens, cfg)

    def of_reference(params, tokens):
        (loss, stats), grads = jax.value_and_grad(
            lambda p: reference_loss(p, tokens, c, dtype, **faults), has_aux=True)(params)
        return loss, optax.global_norm(grads), _checked(grads, c), stats

    return of_system, of_reference


def check(system: System, tokens, *, program=None, reference=None) -> Dict[str, Any]:
    """Loss, global gradient norm, the gradient at ten leaves (`CHECKED_LEAVES`) and the experts chosen, of the
    system's `loss_fn` (through the stream mixes, the flash kernels at two widths and the held-experts layer) against
    the reference's, on `tokens` (a jax array, already placed) with the run's own parameters; what the routers did
    (`routing_stats`: `dropped` must be 0); and `res_sum_err`, the largest distance from 1 of a row or column sum of
    any H_res, the program's and the reference's (both under the limit: twenty rounds leave what they leave, one
    round or none leaves more). A limit is the configuration's own (`check_tolerances`: the rehearsal's toy) where
    it gives one, else this file's. `program`, `(params, tokens) -> (loss, the gradient's norm, its checked leaves,
    stats)`, stands in the system's place (`tools/xing4_readings.py`: the reference a precision below, or under a
    planted fault), and `reference` is what the reference's program gave for these tokens where the caller has run
    it already."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    c = system.c
    own = c.get("check_tolerances", {})
    leaf_tol = own.get("leaf_grad_rel", LEAF_GRAD_REL_TOL)
    if not isinstance(leaf_tol, dict):
        leaf_tol = dict.fromkeys(CHECKED_LEAVES, leaf_tol)
    limits = {"loss_abs_err": own.get("loss_abs", LOSS_ABS_TOL), "grad_norm_rel_err": own.get("grad_norm_rel", GRAD_NORM_REL_TOL),
              "leaf_grad_rel_err": leaf_tol, "expert_choices_flipped_share": own.get("flipped_share", FLIPPED_SHARE_TOL),
              "res_sum_err": own.get("res_sum_err", RES_SUM_ERR_TOL)}
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
        flipped = float(1.0 - jnp.take_along_axis(ref_stats["chosen"], stats.pop("experts"), axis=-1).mean())
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
    sum_errs = np.asarray(stats["res_sum_err"], np.float64)
    out = {
        "loss_system": sys_loss, "loss_reference": ref_loss,
        "grad_norm_system": sys_norm, "grad_norm_reference": ref_norm,
        "loss_abs_err": abs(sys_loss - ref_loss),
        "grad_norm_rel_err": abs(sys_norm - ref_norm) / max(ref_norm, 1e-30),
        "leaf_grad_rel_err": leaf_err,
        "leaf_grad_norm_reference": leaf_ref,
        "expert_choices_flipped_share": flipped,
        "res_sum_err": max(float(sum_errs.max()), float(ref_stats["res_sum_err"])),
        "streams": {"res_sum_err_by_layer": [float(x) for x in sum_errs], "res_sum_err_reference": float(ref_stats["res_sum_err"]),
                    "streams": c["hc_mult"], "rounds": c["hc_sinkhorn_iters"]},
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
        "limits": limits,
    }
    out["over_limit"] = sorted(
        [name for name in ("loss_abs_err", "grad_norm_rel_err", "expert_choices_flipped_share", "res_sum_err")
         if not out[name] <= limits[name]]
        + [f"leaf_grad_rel_err.{name}" for name, err in leaf_err.items() if not err <= leaf_tol[name]])
    out["ok"] = bool(all(map(math.isfinite, got + list(leaf_err.values()))) and not out["over_limit"]
                     and not wrong_dtype and out["routing"]["dropped"] == 0)
    return out
