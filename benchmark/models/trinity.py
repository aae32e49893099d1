"""Trinity for the benchmark: the system under test built through ray_tpu's
public API, a plain float32 reference written from the issue's equations, the
comparison that decides `correct`, and the arithmetic of FLOPs and bytes.

A configuration file (`benchmark/configs/<name>.json`) with `"model":
"trinity"` is served by this module. Keys read, under the names of the source's
`config.json`: `num_hidden_layers`, `num_dense_layers`, `layer_types`,
`hidden_size`, `num_attention_heads`, `num_key_value_heads`, `head_dim`,
`intermediate_size`, `moe_intermediate_size`, `num_experts` (the experts held
here; the router's width is `published.num_experts` where the file cuts the
key), `num_experts_per_tok`, `num_shared_experts`, `route_norm`, `route_scale`,
`score_func`, `load_balance_coeff`, `sliding_window`, `rope_theta`,
`rope_scaling`, `mup_enabled`, `vocab_size`, `rms_norm_eps`,
`tie_word_embeddings`, `n_group`, `topk_group`; and the benchmark's own:
`first_expert_held`, `dtype`, `param_dtype`, `remat_policy`, `learning_rate`
(the peak), `warmup_steps` and `total_steps`.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional

from benchmark.models.lfm2 import _issued_rows, rms_norm  # the same norm, the same grouped kernels
from benchmark.models.olmo_hybrid import _moments_set_aside  # AdamW's zero moments out of the check's way: 4.0 GB here

WINDOW, FULL = "window", "full"

# ------------------------------------------------------------------ arithmetic
# No jax below this line until `build`: the parent and the tests use these.
# Everything counts what this chip computes: the experts it holds, the slice of
# the vocabulary it holds, the layers it holds.


def layer_kinds(c: Dict[str, Any]) -> List[str]:
    """Every layer's kind in the file's order: `window` or `full` by `layer_types`, `dense_` before it in the first
    `num_dense_layers`."""
    of = {"sliding_attention": WINDOW, "full_attention": FULL}
    assert len(c["layer_types"]) == c["num_hidden_layers"]
    return [("dense_" if i < c["num_dense_layers"] else "") + of[t] for i, t in enumerate(c["layer_types"])]


def _layers(c: Dict[str, Any]) -> Dict[str, int]:
    kinds = layer_kinds(c)
    return {"window": sum(k.endswith(WINDOW) for k in kinds), "full": sum(k.endswith(FULL) for k in kinds),
            "dense": c["num_dense_layers"], "moe": len(kinds) - c["num_dense_layers"]}


def router_width(c: Dict[str, Any]) -> int:
    """The experts the router scores: the published count where the file's `num_experts` is the chip's share."""
    return c.get("published", {}).get("num_experts", c["num_experts"])


def held_pairs_per_layer(c: Dict[str, Any], tokens: int) -> float:
    """The (token, expert) pairs an even router gives the experts held here."""
    return tokens * c["num_experts_per_tok"] * c["num_experts"] / router_width(c)


def attention_matmul_params(c: Dict[str, Any]) -> int:
    """One layer's W_q, the output gate W_g and W_o (hidden x heads x head_dim each), W_k and W_v."""
    d, hd = c["hidden_size"], c["head_dim"]
    return 3 * d * c["num_attention_heads"] * hd + 2 * d * c["num_key_value_heads"] * hd


def layer_params(c: Dict[str, Any], dense: bool) -> Dict[str, int]:
    """One layer's parameters here by part: `attention` (the five matrices, two head norms), `norms` (four of
    hidden_size), `ff` (a dense layer's SwiGLU; else the router whole, the selection bias, the shared expert whole
    and the routed experts held)."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    ff = 3 * d * c["intermediate_size"] if dense else (
        d * router_width(c) + router_width(c) + 3 * d * f * (c["num_shared_experts"] + c["num_experts"]))
    return {"attention": attention_matmul_params(c) + 2 * c["head_dim"], "norms": 4 * d, "ff": ff}


def num_params(c: Dict[str, Any]) -> int:
    """Every parameter this chip holds, by hand; the embedding, the final norm and the head (untied)."""
    n = _layers(c)
    return (2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"]
            + n["dense"] * sum(layer_params(c, True).values()) + n["moe"] * sum(layer_params(c, False).values()))


def kept_pairs(c: Dict[str, Any], seq: int, kind: str) -> int:
    """(query, key) pairs of one head that a layer of `kind` keeps on a row of `seq`: a full layer the triangle
    seq (seq + 1) / 2; a window layer query i's min(i + 1, window) keys: a triangle of `window` and then `window` a
    row. 31,458,304 of the triangle's 134,225,920 at 16,384 under 2,048: 23.4 %."""
    w = min(c["sliding_window"], seq) if kind.endswith(WINDOW) else seq
    return w * (w + 1) // 2 + (seq - w) * w


def active_matmul_params(c: Dict[str, Any]) -> float:
    """Matmul parameters a token meets here: every layer's attention, a dense layer's SwiGLU, an expert layer's
    router, shared expert and (in expectation) its pairs' held experts; the head over the vocabulary's slice. The
    embedding is a lookup; norms and the bias multiply nothing on the MXU's scale."""
    d, f, n = c["hidden_size"], c["moe_intermediate_size"], _layers(c)
    moe = d * router_width(c) + 3 * d * f * (c["num_shared_experts"] + held_pairs_per_layer(c, 1))
    return ((n["dense"] + n["moe"]) * attention_matmul_params(c) + n["dense"] * 3 * d * c["intermediate_size"]
            + n["moe"] * moe + c["vocab_size"] * d)


def train_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    """FLOPs the model's mathematics requires per token on this chip, forward and backward: 6 per active matmul
    parameter; attention's six products (two forward, four backward, 2 x head_dim a pair) on the pairs each layer's
    mask keeps: the band in a window layer, the triangle in a full one. Recomputation is not counted, and a
    crossed tile's dropped scores neither."""
    kept = sum(kept_pairs(c, seq, kind) for kind in layer_kinds(c))
    return 6.0 * active_matmul_params(c) + 12.0 * c["num_attention_heads"] * c["head_dim"] * kept / seq


def _flash_flops(c: Dict[str, Any], rows: int, seq: int, kinds: List[str]) -> float:
    kept = sum(kept_pairs(c, seq, kind) for kind in kinds)
    return 12.0 * c["head_dim"] * kept * rows * c["num_attention_heads"]


def _flash_bytes(c: Dict[str, Any], rows: int, seq: int, calls: int) -> float:
    """q, o, do, dq a query head, k, v, dk, dv a key/value head (bf16), the row statistics and delta (f32), each once
    a call: what no walk can avoid, whatever the mask (as `keye_vl2.flash_bytes_per_step` counts grouped heads)."""
    act, stat = seq * c["head_dim"] * 2, seq * 4
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    per_row = nh * (2 * act + stat) + nkv * 2 * act + nh * (4 * act + 2 * stat) + nkv * 4 * act
    return float(per_row * rows * calls)


def flash_flops_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """FLOPs the attention of one train step requires of the two flash kernels, counted from the mask's kept
    scores and not from the tiles: the window layers' band calls and the full layers' triangle."""
    return _flash_flops(c, rows, seq, layer_kinds(c))


def flash_bytes_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    return _flash_bytes(c, rows, seq, c["num_hidden_layers"])


def flash_window_flops_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """`flash_flops_per_step` of the window layers' calls alone."""
    return _flash_flops(c, rows, seq, [k for k in layer_kinds(c) if k.endswith(WINDOW)])


def flash_window_bytes_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    return _flash_bytes(c, rows, seq, _layers(c)["window"])


def moe_expert_flops_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """FLOPs the held experts of one train step require: each pair an even router gives them meets three matrices
    of hidden_size x moe_intermediate_size, 2 FLOPs a parameter forward and 4 backward. The shared expert is
    outside the scope `experts`."""
    pairs = held_pairs_per_layer(c, rows * seq)
    return 6.0 * 3 * c["hidden_size"] * c["moe_intermediate_size"] * pairs * _layers(c)["moe"]


def moe_expert_bytes_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """Bytes the nine grouped products of a step must move in bf16 (as the GLM file counts them)."""
    pairs = held_pairs_per_layer(c, rows * seq)
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    one_product = pairs * d + c["num_experts"] * d * f + pairs * f
    return 2.0 * 3 * 3 * one_product * _layers(c)["moe"]


# ---------------------------------------------------------------------- system
def trinity_config(c: Dict[str, Any]):
    import jax.numpy as jnp

    from ray_tpu.models.trinity import TrinityConfig

    assert c["score_func"] == "sigmoid" and c["n_group"] == c["topk_group"] == 1, "the only routing written"
    assert c["num_shared_experts"] == 1 and not c["tie_word_embeddings"] and c["rope_scaling"] is None
    assert c["hidden_act"] == "silu" and c["route_norm"] is True and c["mup_enabled"] is True
    return TrinityConfig(
        vocab_size=c["vocab_size"], layer_types=tuple(c["layer_types"]), n_layer=c["num_hidden_layers"],
        n_dense_layers=c["num_dense_layers"], n_head=c["num_attention_heads"], n_kv_head=c["num_key_value_heads"],
        head_dim=c["head_dim"], d_model=c["hidden_size"], d_ff=c["intermediate_size"],
        d_expert=c["moe_intermediate_size"], n_experts=router_width(c), experts_per_token=c["num_experts_per_tok"],
        n_experts_held=c["num_experts"], first_expert_held=c.get("first_expert_held", 0),
        route_scale=float(c["route_scale"]),
        load_balance_coeff=float(c["load_balance_coeff"]), sliding_window=c["sliding_window"],
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
        dtype=jnp.dtype(c["dtype"]), param_dtype=jnp.dtype(c["param_dtype"]), remat_policy=c["remat_policy"],
    )


class System:
    """cfg, optimizer, state and jitted step, made as a user makes them."""

    def __init__(self, c: Dict[str, Any], mesh, seed: int):
        import jax

        from ray_tpu.models import create_train_state, default_optimizer, make_train_step

        self.c = c
        self.mesh = mesh
        self.cfg = trinity_config(c)
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
QUERY_BLOCK = 512  # queries whose (block, seq) f32 scores the reference holds at once
HEAD_ROWS = 2048  # positions whose f32 logits are held at once


def layers_in_order(blocks, c: Dict[str, Any]):
    """(kind, the layer's own parameters) of every layer in the file's order, out of the tree the system trains:
    the leading layers one tree each, then one stack for every place in the period, the same place of every
    period on its leading axis."""
    import jax

    kinds = layer_kinds(c)
    own = list(blocks["leading"])
    n_periods = jax.tree.leaves(blocks["period"])[0].shape[0]
    own += [jax.tree.map(lambda a, p=p: a[p], place) for p in range(n_periods) for place in blocks["period"]]
    assert len(own) == len(kinds) and not blocks["trailing"]
    return list(zip(kinds, own))


def bias_rule(bias, counts, coeff: float):
    """The buffer's rule of the issue on one layer, in numpy: `d = coeff x sign(mean(c) - c)`, `b + d - mean(d)`."""
    import numpy as np

    counts = np.asarray(counts, np.float64)
    d = coeff * np.sign(counts.mean() - counts)
    return np.asarray(bias, np.float64) + d - d.mean()


def reference_loss(params, tokens, c: Dict[str, Any], dtype=None, *, window: Optional[int] = None,
                   rope_in_full: bool = False):
    """Trinity's next-token objective (the equations of ISSUE 61; the source's `config.json` fixes the sizes, what
    it does not give is under the configuration's `assumed`) in float32 `jax.numpy`; returns (loss, {"chosen":
    (expert layers, tokens, experts) bool, the experts each token was given among all the router scores}).

    `x = Emb[t] sqrt(hidden_size)`. Every layer, RMSNorm at `rms_norm_eps` with a scale of hidden_size, no bias: `n
    = N_in(x)`; `q = N_q(n W_q)`, `k = N_k(n W_k)` with a norm over each head's own `head_dim`, `v = n W_v`; in a
    window layer a rotate-half rotation of q and k over all of a head at `rope_theta`, in a full layer none; query
    head a on key/value head `a // group`; softmax at `head_dim^-1/2` over the keys j of query i with `i - j >= 0`
    and, in a window layer, `i - j < sliding_window`: the mask is these comparisons of positions, a block of
    `QUERY_BLOCK` queries and one head at a time so that the scores fit; `h = x + N_post_attn((sigmoid(n W_g) * o)
    W_o)`. Then `m = N_pre_mlp(h)` and `y = h + N_post_mlp(ff(m))`: a dense layer's `ff` a SwiGLU of
    `intermediate_size`; an expert layer's `s = sigmoid(m W_r)`, the `num_experts_per_tok` largest of `s + b`, `w =
    route_scale x s` at the chosen over their sum, `shared(m) + sum_e w_e E_e(m)` over the experts this chip holds
    alone, in a loop over them, every held expert on every token weighted by the routing matrix (zero where it was
    not chosen): the partial sum goes on, as in the system. Final norm, untied head, mean cross entropy of the
    next token. No kernel, no tile schedule, no sort, no bf16.

    Departures from a line-by-line transcription, none changes the arithmetic: each layer, each block of queries,
    each head, each expert and each chunk of the head's logits is made again in the backward pass
    (`jax.checkpoint`).

    `dtype` (default float32) computes everything, parameters, norms, rotation, router and logits included, in that
    type instead: what a lower precision than the configuration states would give, for PERF.md's second reading.
    `window` puts another window in the configuration's place and `rope_in_full` rotates in the full layers too:
    planted faults, for the readings a limit lies between."""
    import jax
    import jax.numpy as jnp

    f = jnp.dtype(dtype or jnp.float32)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    batch, seq = inputs.shape
    d, eps, k = c["hidden_size"], c["rms_norm_eps"], c["num_experts_per_tok"]
    nh, nkv, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    group = nh // nkv
    held, first, width = c["num_experts"], c.get("first_expert_held", 0), router_width(c)
    window = c["sliding_window"] if window is None else window
    block_rows = math.gcd(seq, QUERY_BLOCK)

    inv_freq = float(c["rope_theta"]) ** (-jnp.arange(hd // 2, dtype=jnp.float32) / (hd // 2))
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None]
    angles = jnp.concatenate([angles, angles], axis=-1)
    cos, sin = jnp.cos(angles).astype(f), jnp.sin(angles).astype(f)

    def rope(x):  # (heads, seq, head_dim): rotate-half
        return x * cos + jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], axis=-1) * sin

    def heads(n, w):  # (seq, d) x (d, heads, head_dim) -> (heads, seq, head_dim)
        return (n @ w.reshape(d, -1)).reshape(seq, -1, hd).transpose(1, 0, 2)

    def attention_of_row(n, layer, windowed: bool):
        """n (seq, d) normed -> o (seq, heads * head_dim)."""
        q, key = rms_norm(heads(n, layer["wq"]), layer["q_norm"], eps), rms_norm(heads(n, layer["wk"]), layer["k_norm"], eps)
        if windowed or rope_in_full:
            q, key = rope(q), rope(key)
        v = heads(n, layer["wv"])

        @jax.checkpoint
        def query_block(start):
            i, j = (start + jnp.arange(block_rows))[:, None], jnp.arange(seq)[None, :]
            kept = i - j >= 0
            if windowed:
                kept = kept & (i - j < window)

            def head(a):
                s = jax.lax.dynamic_slice_in_dim(q[a], start, block_rows) @ key[a // group].T / jnp.sqrt(jnp.asarray(hd, f))
                return jax.nn.softmax(jnp.where(kept, s, -jnp.inf), axis=-1) @ v[a // group]

            return jax.lax.map(jax.checkpoint(head), jnp.arange(nh))

        o = jax.lax.map(query_block, jnp.arange(0, seq, block_rows))  # (blocks, heads, rows, head_dim)
        return o.transpose(0, 2, 1, 3).reshape(seq, nh * hd)

    def swiglu(m, w_gate, w_up, w_down):
        return (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down

    @jax.checkpoint
    def expert(m, weight, w_gate, w_up, w_down):
        return weight[:, None] * swiglu(m, w_gate, w_up, w_down)

    def experts(m, moe):
        m = m.reshape(batch * seq, d)
        scores = jax.nn.sigmoid(m @ moe["router_w"])
        choice = jax.lax.stop_gradient(scores + moe["expert_bias"])
        chosen = jax.nn.one_hot(jax.lax.top_k(choice, k)[1], width, dtype=bool).any(axis=1)
        weights = jnp.where(chosen, scores, 0.0)
        weights = weights / weights.sum(-1, keepdims=True) * jnp.asarray(c["route_scale"], f)

        def add_expert(y, xs):
            return y + expert(m, *xs), None

        y, _ = jax.lax.scan(add_expert, jnp.zeros_like(m),
                            (weights.T[first:first + held], moe["w_gate"], moe["w_up"], moe["w_down"]))
        y = y + swiglu(m, moe["shared_gate"], moe["shared_up"], moe["shared_down"])
        return y.reshape(batch, seq, d), chosen

    def block(kind):
        @jax.checkpoint
        def apply(x, layer):
            layer = jax.tree.map(lambda p: p.astype(f), layer)
            n = rms_norm(x, layer["attn_norm"], eps)
            o = jnp.stack([attention_of_row(n[b], layer, kind.endswith(WINDOW)) for b in range(batch)])
            gate = jax.nn.sigmoid(n @ layer["wg"].reshape(d, nh * hd))
            h = x + rms_norm((gate * o) @ layer["wo"].reshape(nh * hd, d), layer["post_attn_norm"], eps)
            m = rms_norm(h, layer["mlp_norm"], eps)
            if kind.startswith("dense_"):
                y, chosen = swiglu(m, layer["w_gate"], layer["w_up"], layer["w_down"]), None
            else:
                y, chosen = experts(m, layer["moe"])
            return h + rms_norm(y, layer["post_mlp_norm"], eps), chosen
        return apply

    head_rows = math.gcd(seq, HEAD_ROWS)

    @jax.checkpoint
    def head_chunk(table, xs):
        x, t = xs  # (batch, head_rows, d), (batch, head_rows)
        log_p = jax.nn.log_softmax(x @ table.T, axis=-1)
        return -jnp.take_along_axis(log_p, t[..., None], axis=-1).sum()

    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(f)[inputs] * jnp.asarray(math.sqrt(d), f)
        chosen = []
        for kind, layer in layers_in_order(params["blocks"], c):
            x, of_layer = block(kind)(x, layer)
            if of_layer is not None:
                chosen.append(of_layer)
        x = rms_norm(x, params["final_norm"].astype(f), eps)
        chunks = lambda a: jnp.moveaxis(a.reshape(batch, seq // head_rows, head_rows, *a.shape[2:]), 1, 0)  # noqa: E731
        table = params["lm_head"].astype(f)
        total = jax.lax.map(lambda xs: head_chunk(table, xs), (chunks(x), chunks(targets))).sum()
    return (total / (batch * seq)).astype(jnp.float32), {"chosen": jnp.stack(chosen)}


# Tolerances of the agreement between the system (bf16 activations and matmul operands, the flash kernels under
# the window's mask by structure and under the causal diagonal, grouped matmuls over the held groups; f32 router,
# norms, gate, logits and parameters) and the reference (f32 throughout, masks as comparisons of positions, every
# held expert on every token), at seeded initial weights, on the one row (16,384 tokens) of the run's first batch
# that the harness hands `check`: the timed shape. Measured on the chip at the published widths under the cell's
# own traffic (`tools/trinity_readings.py` and the cell's own runs, PR 61, PERF.md section 6; every seed its own):
# the system, and in the program's place the reference itself a precision below the stated one ("below":
# parameters, norms, rotation, router and logits in bf16) or under a planted fault (the diagonal alone in the window
# layers; a rotation in the full layer; a window of 2,047 or 2,049 keys; and the program itself at 2,047).
#   loss            system 5.7e-6..4.1e-4 (17 readings); below 3.08e-2, 3.87e-2: the limit that tells the precision,
#                   seven times the system's largest and a tenth of below's least. No fault of the mask moves it (<= 1.5e-2)
#   gradient norm   system 6.4e-6..8.7e-4 (17); below 1.9e-4, 5.2e-4 (it cannot tell the precision); the diagonal alone
#                   1.42e-2, 3.06e-2; a rotation in the full layer 3.1e-3, 4.7e-3: 2.3 times the system's largest,
#                   two thirds of the rotation's least
#   flipped choices system 1.10..1.22 % (17) of the 524,288 (token, slot) choices of the four routers; below 1.84, 1.88;
#                   a window of 2,047 or 2,049 in the reference 1.54..1.56, in the program itself 1.95, 1.96; a
#                   rotation in the full layer 2.02; the diagonal alone 60, 62: a quarter above the system's largest,
#                   a fifth under below's least. The one reading that tells the window's off-by-one on the chip, and
#                   by a third: one key in 2,048 moves a score's softmax by less than bf16 rounds it, and what reaches
#                   any gradient is the 0.4 % of choices it turns, as rounding turns 1.1 % (below). It is held in
#                   float32 on the CPU where nothing is turned (`tests/test_trinity.py`, `tests/test_flash_window.py`)
# The gradient at a leaf, `|system - reference| / |reference|` (not a difference of norms: a leaf whose gradient
# points elsewhere at the right length is told): of the first window layer of the period everything that is new
# (the gate, the four norms' scales, the head norms, the router, the shared expert) and W_q, W_k, which reach the
# loss through the masked scores alone; of the full layer W_q and W_k apart. At these widths every leaf reads alike,
# because the turned choices move the whole backward signal: system 0.030..0.053 over 17 seeds (the head norms' and
# N_pre_mlp's scales the largest), below 0.044..0.071, the off-by-one 0.039..0.074: none of these can tell a precision or one
# key. What they tell is another function: a rotation in the full layer reads 1.12..1.15 at the full layer's W_q and
# W_k and 0.13..0.20 at every other leaf, the diagonal alone 0.87..1.26 everywhere: 0.09 is 1.7 times the system's
# largest and two thirds of the rotation's least. The router's own gradient follows the turned choices (a turned
# pair moves a whole row of it): system 0.15..0.20, below 0.24, 0.25, the diagonal alone 1.12, 1.22: its limit is
# there for another function (scores that sum to one, a weight left unscaled: 1 and more).
# The buffer: the program's rule on its own counts against the rule in numpy reads 6e-11..1.3e-10 (f32's last bit of
# 0.001), and against the rule on the reference's counts, at the 506-512 of 512 experts whose count rounding did not
# carry across the mean (0-4 were), 0..1.5e-10; a sign read otherwise, or `mean(d)` left in, moves an entry by 1e-3 and more.
LOSS_ABS_TOL = 3e-3
GRAD_NORM_REL_TOL = 2e-3
FLIPPED_SHARE_TOL = 1.5e-2
BIAS_ABS_TOL = 1e-6
WINDOW_LEAVES = ("wq", "wk", "wg", "q_norm", "k_norm", "attn_norm", "post_attn_norm", "mlp_norm", "post_mlp_norm",
                 "router_w", "shared_gate", "shared_up", "shared_down")
FULL_LEAVES = ("wq", "wk")
CHECKED_LEAVES = tuple(f"window.{n}" for n in WINDOW_LEAVES) + tuple(f"full.{n}" for n in FULL_LEAVES)
LEAF_GRAD_REL_TOL = {**dict.fromkeys(CHECKED_LEAVES, 0.09), "window.router_w": 0.5}


def _checked(grads, c: Dict[str, Any]):
    """The gradient at each of `CHECKED_LEAVES`, f32: the period's first window layer's and its first full layer's
    (the first period's), out of the tree the system trains."""
    import jax.numpy as jnp

    n_lead = len(grads["blocks"]["leading"])
    period = layer_kinds(c)[n_lead:n_lead + len(grads["blocks"]["period"])]
    window, full = (grads["blocks"]["period"][period.index(kind)] for kind in (WINDOW, FULL))
    of = lambda tree, name: (tree["moe"][name] if name in tree["moe"] else tree[name])[0]  # noqa: E731
    leaves = [of(window, name) for name in WINDOW_LEAVES] + [of(full, name) for name in FULL_LEAVES]
    return [leaf.astype(jnp.float32) for leaf in leaves]


def _counts_in_order(counts):
    """(expert layers, experts): `loss_fn`'s statistics, laid out as the parameters' `blocks`, in the layers' order."""
    import jax.numpy as jnp

    own = [x for x in counts["leading"] if x is not None]
    places = [place for place in counts["period"] if place is not None]
    own += [place[p] for p in range(places[0].shape[0]) for place in places]
    return jnp.stack(own)


def losses_and_grads(system: System, dtype=None, cfg=None, **faults):
    """(of_system, of_reference): each `(params, tokens) -> (loss, the gradient's global norm, its checked
    leaves, more)`, a program each so that the two gradient trees (2.0 GB each at the published widths) are never
    held at once. The system's `more` is its `routing_stats` and the selection biases after its own rule on its own
    counts (`update_buffers`, what the step applies; `step_counts` are those counts), the reference's its chosen experts. `cfg` puts another
    configuration of the program in the system's place, `dtype` and `faults` are `reference_loss`'s."""
    import jax
    import optax

    from ray_tpu.models import trinity

    cfg, mesh, c = cfg or system.cfg, system.mesh, system.c

    def of_system(params, tokens):
        (loss, counts), grads = jax.value_and_grad(
            lambda p: trinity.loss_fn(p, {"tokens": tokens}, cfg, mesh=mesh), has_aux=True)(params)
        stats = trinity.routing_stats(params, tokens[:, :-1], cfg)
        moved = trinity.update_buffers(params, counts, cfg)
        stats["step_counts"] = _counts_in_order(counts)
        stats["bias_moved"] = [layer["moe"]["expert_bias"] for _, layer in layers_in_order(moved["blocks"], c)
                               if "moe" in layer]
        stats["bias_before"] = [layer["moe"]["expert_bias"] for _, layer in layers_in_order(params["blocks"], c)
                                if "moe" in layer]
        return loss, optax.global_norm(grads), _checked(grads, c), stats

    def of_reference(params, tokens):
        (loss, stats), grads = jax.value_and_grad(
            lambda p: reference_loss(p, tokens, c, dtype, **faults), has_aux=True)(params)
        return loss, optax.global_norm(grads), _checked(grads, c), stats

    return of_system, of_reference


def check(system: System, tokens, *, program=None, reference=None) -> Dict[str, Any]:
    """Loss, global gradient norm, the gradient at fifteen leaves (`CHECKED_LEAVES`) and the experts chosen, of the
    system's `loss_fn` (through the flash kernels under both masks and the held-experts layer) against the
    reference's, on `tokens` (a jax array, already placed) with the run's own parameters; what the routers did
    (`routing_stats`: `dropped` must be 0); and the buffer: the selection biases after the program's rule on the
    program's counts against the rule written in numpy on the same counts (`bias_rule_abs_err`), and against the
    rule on the reference's counts at the experts whose reference count stands further from the layer's mean (the
    same for both: tokens x k / experts) than rounding's flipped choices moved the program's count from it, the
    common offset of the others' signs taken out (`bias_abs_err_far`): there no rounding can have turned the sign. A limit is the configuration's own (`check_tolerances`: the rehearsal's
    toy) where it gives one, else this file's. `program`, `(params, tokens) -> (loss, the gradient's norm, its
    checked leaves, stats)`, stands in the system's place (`tools/trinity_readings.py`: the reference a precision
    below, or under a planted fault), and `reference` is what the reference's program gave for these tokens
    where the caller has run it already."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.flash_attention import SlidingWindow, kernel_plan

    c, cfg = system.c, system.cfg
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
        same = jnp.take_along_axis(ref_stats["chosen"], stats.pop("experts"), axis=-1)
        flipped_by_layer = np.asarray((~same).sum(axis=(1, 2)))
        flipped = float(1.0 - same.mean())
        ref_counts = np.asarray(ref_stats["chosen"].sum(axis=1))  # (layers, experts)
        norm = lambda x: float(jnp.sqrt(jnp.sum(jnp.square(x))))  # noqa: E731
        leaf_err = {name: norm(a - b) / max(norm(b), 1e-30)
                    for name, a, b in zip(CHECKED_LEAVES, sys_leaves, ref_leaves)}
        leaf_ref = {name: norm(b) for name, b in zip(CHECKED_LEAVES, ref_leaves)}
        del sys_leaves, ref_leaves, reference, same
    got = [float(x) for x in (sys_loss, sys_norm, ref_loss, ref_norm)]
    sys_loss, sys_norm, ref_loss, ref_norm = got
    stats = jax.device_get(stats)
    per_expert = stats["tokens_per_expert"]
    # The buffer, a layer at a time, on the counts the loss itself handed the rule.
    coeff, rule_err, far_err, far, near_flips, after = float(c["load_balance_coeff"]), 0.0, 0.0, 0, 0, 0.0
    step_counts = stats.pop("step_counts")
    for layer, (before, moved) in enumerate(zip(stats.pop("bias_before"), stats.pop("bias_moved"))):
        after = max(after, float(np.abs(moved).max()))
        rule_err = max(rule_err, float(np.abs(moved - bias_rule(before, step_counts[layer], coeff)).max()))
        by_reference = bias_rule(before, ref_counts[layer], coeff)
        clear = np.abs(ref_counts[layer] - ref_counts[layer].mean()) > np.abs(step_counts[layer] - ref_counts[layer])
        far += int(clear.sum())
        if clear.any():
            off = (moved - by_reference)[clear]
            far_err = max(far_err, float(np.abs(off - np.median(off)).max()))
        near_flips += int((np.sign(step_counts[layer].mean() - step_counts[layer])
                           != np.sign(ref_counts[layer].mean() - ref_counts[layer])).sum())
    held, elsewhere = int(stats["held_pairs"].sum()), int(stats["elsewhere_pairs"].sum())
    first = c.get("first_expert_held", 0)
    held_sizes = [[int(x) for x in layer[first:first + c["num_experts"]]] for layer in per_expert]
    seq = tokens.shape[1] - 1
    mask = SlidingWindow(cfg.sliding_window)
    plan = kernel_plan((tokens.shape[0], cfg.n_head, seq, cfg.head_dim), mask, kv_heads=cfg.n_kv_head)
    out = {
        "loss_system": sys_loss, "loss_reference": ref_loss,
        "grad_norm_system": sys_norm, "grad_norm_reference": ref_norm,
        "loss_abs_err": abs(sys_loss - ref_loss),
        "grad_norm_rel_err": abs(sys_norm - ref_norm) / max(ref_norm, 1e-30),
        "leaf_grad_rel_err": leaf_err,
        "leaf_grad_norm_reference": leaf_ref,
        "expert_choices_flipped_share": flipped,
        "bias_rule_abs_err": rule_err,
        "bias_abs_err_far": far_err,
        "bias": {"experts_compared_far": far, "experts": int(ref_counts.size), "signs_turned_near_the_mean": near_flips,
                 "flipped_choices_by_layer": [int(x) for x in flipped_by_layer],
                 "abs_max_before": float(stats["bias_abs_max"].max()), "abs_max_after": after,
                 "counts_differ_between_the_loss_and_routing_stats": int((step_counts != per_expert).sum())},
        "state_dtypes_other_than_stated": wrong_dtype,
        "swa": {"window": cfg.sliding_window, "tiles": [plan.tile_q, plan.tile_k], "walked_tiles": plan.tiles_visited,
                "crossed_tiles": plan.tiles_masked, "all_tiles": plan.tiles_total,
                "kept_pairs_per_head": kept_pairs(c, seq, WINDOW), "kept_over_triangle": kept_pairs(c, seq, WINDOW) / kept_pairs(c, seq, FULL)},
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
            "load_max_over_mean_by_layer": [float(x) for x in stats["load_max_over_mean"]],
            "tokens_per_expert_min": int(per_expert.min()),
            "tokens_per_expert_max": int(per_expert.max()),
        },
        "limits": {"loss_abs_err": loss_tol, "grad_norm_rel_err": grad_tol, "leaf_grad_rel_err": leaf_tol,
                   "expert_choices_flipped_share": flipped_tol, "bias_rule_abs_err": BIAS_ABS_TOL,
                   "bias_abs_err_far": BIAS_ABS_TOL},
    }
    out["over_limit"] = sorted(
        [name for name in ("loss_abs_err", "grad_norm_rel_err", "expert_choices_flipped_share", "bias_rule_abs_err",
                           "bias_abs_err_far") if not out[name] <= out["limits"][name]]
        + [f"leaf_grad_rel_err.{name}" for name, err in leaf_err.items() if not err <= leaf_tol[name]])
    out["ok"] = bool(all(map(math.isfinite, got + list(leaf_err.values()))) and not out["over_limit"]
                     and not wrong_dtype and out["routing"]["dropped"] == 0)
    return out
