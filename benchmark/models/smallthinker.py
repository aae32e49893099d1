"""SmallThinker for the benchmark: the system under test built through ray_tpu's
public API, a plain float32 reference written from the issue's equations, the
comparison that decides `correct`, and the arithmetic of FLOPs and bytes.

A configuration file (`benchmark/configs/<name>.json`) with `"model":
"smallthinker"` is served by this module. Keys read, under the names of the
source's `config.json`: `num_hidden_layers`, `rope_layout`,
`sliding_window_layout`, `sliding_window_size`, `hidden_size`,
`num_attention_heads`, `num_key_value_heads`, `head_dim`, `moe_ffn_hidden_size`,
`moe_num_primary_experts` (the experts held here; the router's width is
`published.moe_num_primary_experts` where the file cuts the key),
`moe_num_active_primary_experts`, `moe_primary_router_apply_softmax`,
`norm_topk_prob`, `rope_theta`, `rope_scaling`, `vocab_size`, `rms_norm_eps`,
`tie_word_embeddings`; and the benchmark's own: `first_expert_held`, `dtype`,
`param_dtype`, `remat_policy`, `learning_rate` (the peak), `warmup_steps` and
`total_steps`.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional

from benchmark.models.lfm2 import _issued_rows, rms_norm  # the same norm, the same grouped kernels
from benchmark.models.olmo_hybrid import _moments_set_aside  # AdamW's zero moments out of the check's way: 4.5 GB here
from benchmark.models.trinity import _flash_bytes  # what no walk of grouped heads can avoid moving, whatever the mask

WINDOW, FULL = "window", "full"

# ------------------------------------------------------------------ arithmetic
# No jax below this line until `build`: the parent and the tests use these.
# Everything counts what this chip computes: the experts it holds, the slice of
# the vocabulary it holds, the layers it holds.


def layer_kinds(c: Dict[str, Any]) -> List[str]:
    """Every layer's kind in the file's order: `window` where `sliding_window_layout` is 1 (and `rope_layout` with
    it: the layer rotates), `full` where both are 0 (no position at all)."""
    assert len(c["sliding_window_layout"]) == len(c["rope_layout"]) == c["num_hidden_layers"]
    assert c["sliding_window_layout"] == c["rope_layout"], "the two kinds written"
    return [WINDOW if windowed else FULL for windowed in c["sliding_window_layout"]]


def router_width(c: Dict[str, Any]) -> int:
    """The experts the router scores: the published count where the file's `moe_num_primary_experts` is the chip's share."""
    return c.get("published", {}).get("moe_num_primary_experts", c["moe_num_primary_experts"])


def held_pairs_per_layer(c: Dict[str, Any], tokens: int) -> float:
    """The (token, expert) pairs an even router gives the experts held here."""
    return tokens * c["moe_num_active_primary_experts"] * c["moe_num_primary_experts"] / router_width(c)


def attention_matmul_params(c: Dict[str, Any]) -> int:
    """One layer's W_q and W_o (hidden x heads x head_dim each), W_k and W_v."""
    d, hd = c["hidden_size"], c["head_dim"]
    return 2 * d * c["num_attention_heads"] * hd + 2 * d * c["num_key_value_heads"] * hd


def layer_params(c: Dict[str, Any]) -> Dict[str, int]:
    """One layer's parameters here by part: `attention` (the four matrices), `norms` (two of hidden_size), `router`
    (whole) and `experts` (those held)."""
    d, f = c["hidden_size"], c["moe_ffn_hidden_size"]
    return {"attention": attention_matmul_params(c), "norms": 2 * d, "router": d * router_width(c),
            "experts": 3 * d * f * c["moe_num_primary_experts"]}


def num_params(c: Dict[str, Any]) -> int:
    """Every parameter this chip holds, by hand; the embedding, the final norm and the head (untied)."""
    return (2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"]
            + c["num_hidden_layers"] * sum(layer_params(c).values()))


def kept_pairs(c: Dict[str, Any], seq: int, kind: str) -> int:
    """(query, key) pairs of one head that a layer of `kind` keeps on a row of `seq`: a full layer the triangle
    seq (seq + 1) / 2; a window layer query i's min(i + 1, window) keys: a triangle of `window` and then `window` a
    row. 58,722,304 of the triangle's 134,225,920 at 16,384 under 4,096: 43.75 %."""
    w = min(c["sliding_window_size"], seq) if kind == WINDOW else seq
    return w * (w + 1) // 2 + (seq - w) * w


def active_matmul_params(c: Dict[str, Any]) -> float:
    """Matmul parameters a token meets here: every layer's attention and router and (in expectation) its pairs'
    held experts; the head over the vocabulary's slice. The embedding is a lookup."""
    d, f = c["hidden_size"], c["moe_ffn_hidden_size"]
    layer = attention_matmul_params(c) + d * router_width(c) + 3 * d * f * held_pairs_per_layer(c, 1)
    return c["num_hidden_layers"] * layer + c["vocab_size"] * d


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


def flash_flops_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """FLOPs the attention of one train step requires of the two flash kernels, counted from the mask's kept
    scores and not from the tiles: the window layers' band calls and the full layers' triangle."""
    return _flash_flops(c, rows, seq, layer_kinds(c))


def flash_bytes_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    return _flash_bytes(c, rows, seq, c["num_hidden_layers"])


def flash_window_flops_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """`flash_flops_per_step` of the window layers' calls alone."""
    return _flash_flops(c, rows, seq, [k for k in layer_kinds(c) if k == WINDOW])


def flash_window_bytes_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    return _flash_bytes(c, rows, seq, layer_kinds(c).count(WINDOW))


def moe_expert_flops_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """FLOPs the held experts of one train step require: each pair an even router gives them meets three matrices
    of hidden_size x moe_ffn_hidden_size, 2 FLOPs a parameter forward and 4 backward."""
    pairs = held_pairs_per_layer(c, rows * seq)
    return 6.0 * 3 * c["hidden_size"] * c["moe_ffn_hidden_size"] * pairs * c["num_hidden_layers"]


def moe_expert_bytes_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """Bytes the nine grouped products of a step must move in bf16 (as the GLM file counts them)."""
    pairs = held_pairs_per_layer(c, rows * seq)
    d, f = c["hidden_size"], c["moe_ffn_hidden_size"]
    one_product = pairs * d + c["moe_num_primary_experts"] * d * f + pairs * f
    return 2.0 * 3 * 3 * one_product * c["num_hidden_layers"]


# ---------------------------------------------------------------------- system
def smallthinker_config(c: Dict[str, Any]):
    import jax.numpy as jnp

    from ray_tpu.models.smallthinker import SmallThinkerConfig

    assert c["moe_primary_router_apply_softmax"] is True and c["norm_topk_prob"] is True, "the only routing written"
    assert not c["tie_word_embeddings"] and c["rope_scaling"] is None
    layer_kinds(c)
    return SmallThinkerConfig(
        vocab_size=c["vocab_size"], n_layer=c["num_hidden_layers"],
        sliding_window_layout=tuple(c["sliding_window_layout"]), rope_layout=tuple(c["rope_layout"]),
        n_head=c["num_attention_heads"], n_kv_head=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_model=c["hidden_size"], d_expert=c["moe_ffn_hidden_size"], n_experts=router_width(c),
        experts_per_token=c["moe_num_active_primary_experts"], n_experts_held=c["moe_num_primary_experts"],
        first_expert_held=c.get("first_expert_held", 0), sliding_window=c["sliding_window_size"],
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
        self.cfg = smallthinker_config(c)
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
    one stack for every place in the period, the same place of every period on its leading axis."""
    import jax

    kinds = layer_kinds(c)
    n_periods = jax.tree.leaves(blocks["period"])[0].shape[0]
    own = [jax.tree.map(lambda a, p=p: a[p], place) for p in range(n_periods) for place in blocks["period"]]
    assert len(own) == len(kinds) and not blocks["leading"] and not blocks["trailing"]
    return list(zip(kinds, own))


def expert_layer(tap, m, moe, k: int, held: range, activation):
    """The expert layer on tokens as rows: the router reads `tap` (tokens, d), the experts read `m` (tokens, d).
    `logits = tap W_r`, the k largest, `w` the softmax over those alone; `y = sum over the experts in `held` of w_e
    W_down,e (activation(W_gate,e m) * W_up,e m)`, a loop over them, every one on every token, weighted by the
    routing matrix (zero where it was not chosen); `moe`'s expert axes are as long as `held`. -> (y; chosen
    (tokens, experts) bool among all the router scores; the hidden units of the held (token, expert) pairs whose gate
    is above zero; those pairs)."""
    import jax
    import jax.numpy as jnp

    logits = tap @ moe["router_w"]
    chosen = jax.nn.one_hot(jax.lax.top_k(jax.lax.stop_gradient(logits), k)[1], logits.shape[-1], dtype=bool).any(axis=1)
    weights = jax.nn.softmax(jnp.where(chosen, logits, -jnp.inf), axis=-1)

    @jax.checkpoint
    def expert(m, weight, took, w_gate, w_up, w_down):
        gate = m @ w_gate
        live = jnp.sum((gate > 0) & took[:, None], dtype=jnp.int32)
        return weight[:, None] * ((activation(gate) * (m @ w_up)) @ w_down), live

    def add_expert(carry, xs):
        y, live = carry
        out, lived = expert(m, *xs)
        return (y + out, live + lived), None

    mine = slice(held.start, held.stop)
    (y, live), _ = jax.lax.scan(add_expert, (jnp.zeros_like(m), jnp.zeros((), jnp.int32)),
                                (weights.T[mine], chosen.T[mine], moe["w_gate"], moe["w_up"], moe["w_down"]))
    return y, chosen, live, chosen[:, mine].sum()


def reference_loss(params, tokens, c: Dict[str, Any], dtype=None, *, window: Optional[int] = None,
                   rope_in_full: bool = False, router_reads: str = "input", act: str = "relu"):
    """SmallThinker's next-token objective (the equations of ISSUE 70; the source's `config.json` fixes the sizes,
    what it does not give is under the configuration's `assumed`) in float32 `jax.numpy`; returns (loss, {"chosen":
    (layers, tokens, experts) bool, the experts each token was given among all the router scores; "relu_live":
    (layers,) the hidden units of the held (token, expert) pairs that the activation leaves above zero; "held_pairs"
    (layers,)}).

    `x = Emb[t]`. Every layer, RMSNorm at `rms_norm_eps` with a scale of hidden_size, no bias: `n = N_in(x)`;
    `logits = n W_r`, the `moe_num_active_primary_experts` largest, `w` the softmax over those alone (a softmax over
    all, the chosen renormalised: the same numbers): the router reads n, the layer's normed input, before attention.
    `q = n W_q`, `k = n W_k`, `v = n W_v`, no bias, no norm; in a window layer a rotate-half rotation of q and k over
    all of a head at `rope_theta`, in a full layer none; query head a on key/value head `a // group`; softmax at
    `head_dim^-1/2` over the keys j of query i with `i - j >= 0` and, in a window layer, `i - j <
    sliding_window_size`: the mask is these comparisons of positions, a block of `QUERY_BLOCK` queries and one head
    at a time so that the scores fit; `h = x + o W_o`. Then `m = N_post(h)` and `y = h + sum_e w_e W_down,e
    (relu(W_gate,e m) * W_up,e m)` over the experts this chip holds alone, in a loop over them, every held expert on
    every token weighted by the routing matrix (zero where it was not chosen): the partial sum goes on, as in the
    system. Final norm, untied head, mean cross entropy of the next token. No kernel, no tile schedule, no sort, no
    bf16.

    Departures from a line-by-line transcription, none changes the arithmetic: each layer, each block of queries,
    each head, each expert and each chunk of the head's logits is made again in the backward pass
    (`jax.checkpoint`).

    `dtype` (default float32) computes everything, parameters, norms, rotation, router and logits included, in that
    type instead: what a lower precision than the configuration states would give, for PERF.md's second reading.
    Planted faults, for the readings a limit lies between: `window` puts another window in the configuration's
    place, `rope_in_full` rotates in the full layers too, `router_reads="post_attention"` moves the router's tap to
    m, `act="silu"` is SwiGLU."""
    import jax
    import jax.numpy as jnp

    f = jnp.dtype(dtype or jnp.float32)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    batch, seq = inputs.shape
    d, eps, k = c["hidden_size"], c["rms_norm_eps"], c["moe_num_active_primary_experts"]
    nh, nkv, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    group = nh // nkv
    first = c.get("first_expert_held", 0)
    held = range(first, first + c["moe_num_primary_experts"])
    window = c["sliding_window_size"] if window is None else window
    block_rows = math.gcd(seq, QUERY_BLOCK)
    activation = {"relu": lambda g: jnp.maximum(g, 0), "silu": jax.nn.silu}[act]

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
        q, key, v = heads(n, layer["wq"]), heads(n, layer["wk"]), heads(n, layer["wv"])
        if windowed or rope_in_full:
            q, key = rope(q), rope(key)

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

    def block(kind):
        @jax.checkpoint
        def apply(x, layer):
            layer = jax.tree.map(lambda p: p.astype(f), layer)
            n = rms_norm(x, layer["attn_norm"], eps)
            o = jnp.stack([attention_of_row(n[b], layer, kind == WINDOW) for b in range(batch)])
            h = x + o @ layer["wo"].reshape(nh * hd, d)
            m = rms_norm(h, layer["mlp_norm"], eps)
            tap = {"input": n, "post_attention": m}[router_reads]
            y, chosen, live, pairs = expert_layer(tap.reshape(batch * seq, d), m.reshape(batch * seq, d), layer["moe"],
                                                  k, held, activation)
            return h + y.reshape(batch, seq, d), (chosen, live, pairs)
        return apply

    head_rows = math.gcd(seq, HEAD_ROWS)

    @jax.checkpoint
    def head_chunk(table, xs):
        x, t = xs  # (batch, head_rows, d), (batch, head_rows)
        log_p = jax.nn.log_softmax(x @ table.T, axis=-1)
        return -jnp.take_along_axis(log_p, t[..., None], axis=-1).sum()

    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(f)[inputs]
        of_layers = []
        for kind, layer in layers_in_order(params["blocks"], c):
            x, of_layer = block(kind)(x, layer)
            of_layers.append(of_layer)
        x = rms_norm(x, params["final_norm"].astype(f), eps)
        chunks = lambda a: jnp.moveaxis(a.reshape(batch, seq // head_rows, head_rows, *a.shape[2:]), 1, 0)  # noqa: E731
        table = params["lm_head"].astype(f)
        total = jax.lax.map(lambda xs: head_chunk(table, xs), (chunks(x), chunks(targets))).sum()
    chosen, live, pairs = (jnp.stack(leaves) for leaves in zip(*of_layers))
    return (total / (batch * seq)).astype(jnp.float32), {"chosen": chosen, "relu_live": live, "held_pairs": pairs}


# Tolerances of the agreement between the system (bf16 activations and matmul operands, the flash kernels at groups
# of 7 under the window's mask by structure and under the causal diagonal, grouped matmuls over the held groups; f32
# router, norms, logits and parameters) and the reference (f32 throughout, masks as comparisons of positions, every
# held expert on every token), at seeded initial weights, on the one row (16,384 tokens) of the run's first batch
# that the harness hands `check`: the timed shape. Measured on the chip at the published widths under the cell's own
# traffic (`tools/smallthinker_readings.py` and the cell's own runs, PR 70, PERF.md section 6; every seed its own):
# the system, and in the program's place the reference itself a precision below the stated one ("below": parameters,
# norms, rotation, router and logits in bf16) or under a planted fault (the router's tap moved behind attention;
# SwiGLU; a window of 4,095 keys; a rotation in the full layer).
#   gradient norm   system 3.6e-5..5.8e-5 (12 readings over 12 seeds: the traced run, six untraced runs of the cell, five
#                   of the tool); below 1.92e-4..2.36e-4 (5 seeds): **the limit that tells the precision**, 1.9 times
#                   the system's largest and 1.75 times under below's least; SwiGLU 5.7e-3; the tap moved 4e-6..3.5e-5 (not told)
#   loss            system 1.9e-6..9.9e-5 (12); below 3.0e-4, 1.62e-2, 1.98e-2, 2.21e-2, 2.39e-2: its own loss is a bf16
#                   sum, on a grid of 0.06 at 10.4, so one seed in five lands within 3e-4 and the loss cannot tell the
#                   precision on every seed; the limit stands five times over the system's largest and tells four of
#                   below's five. No planted fault moves it (<= 2.2e-4): at seeded weights a token's logits hardly
#                   hear the experts or the attention
#   flipped choices system 0.382..0.419 % (12) of the 393,216 (token, slot) choices of the four routers; below 0.48..0.49;
#                   SwiGLU 0.85..0.87, a rotation in the full layer 0.98..0.99, the tap moved 1.61..1.65 (another tensor,
#                   another choice; h = x + 0.1 of attention at seeded weights, so most choices survive); a window of
#                   4,095 0.007..0.010: 1.43 times the system's largest, 1.42 times under SwiGLU's least
# The gradient at a leaf, `|system - reference| / |reference|` (not a difference of norms: a leaf whose gradient
# points elsewhere at the right length is told): of the period's first window layer W_q, W_k, both norms' scales and
# the router, of the full layer W_q, W_k and the router. Readings, system (12) | below (5) | the faults' least:
#   W_q, W_k (both layers)  0.0112..0.0132 | 0.0139..0.0161 | 0.033 (SwiGLU), 0.034 (tap), 0.078 and 0.98 (rotation)
#   window.attn_norm        0.032..0.043   | 0.040..0.045   | 0.090 (rotation), 0.186 (SwiGLU), 0.608 (tap)
#   window.mlp_norm         0.057..0.064   | 0.063..0.071   | 0.113 (rotation), 0.329 (SwiGLU), 0.601 (tap)
#   window.router_w         0.051..0.071   | 0.063..0.074   | 0.122 (rotation), 0.134 (tap), 0.306 (SwiGLU)
#   full.router_w           0.048..0.077   | 0.056..0.078   | 0.131 (tap), 0.288 (SwiGLU); a rotation in the full layer 0.022
# No leaf tells the precision (below reads inside 1.25 times the system's largest everywhere); each limit stands 1.3 to
# 1.6 times over the system's largest and as far under the least of the faults it tells, the routers' twice over the
# system's (their gradient follows the turned choices, a turned pair moves a whole row of it) and twice under SwiGLU's.
# A window of 4,095 keys reads under the system's own rounding at every reading (leaves 0.001..0.009, 0.01 % of
# choices): one key in 4,096 cannot be told on the chip; it is held in float32 on the CPU (`tests/test_smallthinker.py`,
# `tests/test_flash_window.py`).
# `relu_live_share`, the program's count against the reference's, a layer at a time: system 6.7e-6..3.1e-5 (12), below
# and every fault 1.4e-6..4.8e-5 (the count reads the gate's sign, whatever the activation does with it): the limit
# tells no precision and no activation; it holds `moe.relu_live_share` to the reference's count (a count of every
# unit, or of every pair, reads 0.5 off).
LOSS_ABS_TOL = 5e-4
GRAD_NORM_REL_TOL = 1.1e-4
FLIPPED_SHARE_TOL = 6e-3
RELU_LIVE_ABS_TOL = 3e-4
WINDOW_LEAVES = ("wq", "wk", "attn_norm", "mlp_norm", "router_w")
FULL_LEAVES = ("wq", "wk", "router_w")
CHECKED_LEAVES = tuple(f"window.{n}" for n in WINDOW_LEAVES) + tuple(f"full.{n}" for n in FULL_LEAVES)
LEAF_GRAD_REL_TOL = {**dict.fromkeys(("window.wq", "window.wk", "full.wq", "full.wk"), 0.021), "window.attn_norm": 0.062,
                     "window.mlp_norm": 0.085, "window.router_w": 0.15, "full.router_w": 0.15}


def _checked(grads, c: Dict[str, Any]):
    """The gradient at each of `CHECKED_LEAVES`, f32: the period's first window layer's and its first full layer's
    (the first period's), out of the tree the system trains."""
    import jax.numpy as jnp

    period = layer_kinds(c)[:len(grads["blocks"]["period"])]
    window, full = (grads["blocks"]["period"][period.index(kind)] for kind in (WINDOW, FULL))
    of = lambda tree, name: (tree["moe"][name] if name in tree["moe"] else tree[name])[0]  # noqa: E731
    leaves = [of(window, name) for name in WINDOW_LEAVES] + [of(full, name) for name in FULL_LEAVES]
    return [leaf.astype(jnp.float32) for leaf in leaves]


def losses_and_grads(system: System, dtype=None, cfg=None, **faults):
    """(of_system, of_reference): each `(params, tokens) -> (loss, the gradient's global norm, its checked
    leaves, more)`, a program each so that the two gradient trees (2.2 GB each at the published widths) are never
    held at once. The system's `more` is its `routing_stats`, the reference's its chosen experts and live count.
    `cfg` puts another configuration of the program in the system's place, `dtype` and `faults` are
    `reference_loss`'s."""
    import jax
    import optax

    from ray_tpu.models import smallthinker

    cfg, mesh, c = cfg or system.cfg, system.mesh, system.c

    def of_system(params, tokens):
        loss, grads = jax.value_and_grad(lambda p: smallthinker.loss_fn(p, {"tokens": tokens}, cfg, mesh=mesh))(params)
        return loss, optax.global_norm(grads), _checked(grads, c), smallthinker.routing_stats(params, tokens[:, :-1], cfg)

    def of_reference(params, tokens):
        (loss, stats), grads = jax.value_and_grad(
            lambda p: reference_loss(p, tokens, c, dtype, **faults), has_aux=True)(params)
        return loss, optax.global_norm(grads), _checked(grads, c), stats

    return of_system, of_reference


def check(system: System, tokens, *, program=None, reference=None) -> Dict[str, Any]:
    """Loss, global gradient norm, the gradient at eight leaves (`CHECKED_LEAVES`), the experts chosen and the share
    of hidden units the activation leaves live, of the system's `loss_fn` (through the flash kernels at groups of 7
    under both masks and the held-experts layer in its two halves) against the reference's, on `tokens` (a jax
    array, already placed) with the run's own parameters; and what the routers did (`routing_stats`: `dropped` must
    be 0). A limit is the configuration's own (`check_tolerances`: the rehearsal's toy) where it gives one, else this
    file's. `program`, `(params, tokens) -> (loss, the gradient's norm, its checked leaves, stats)`, stands in the
    system's place (`tools/smallthinker_readings.py`: the reference a precision below, or under a planted fault),
    and `reference` is what the reference's program gave for these tokens where the caller has run it already."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.flash_attention import SlidingWindow, kernel_plan

    c, cfg = system.c, system.cfg
    own = c.get("check_tolerances", {})
    loss_tol = own.get("loss_abs", LOSS_ABS_TOL)
    grad_tol = own.get("grad_norm_rel", GRAD_NORM_REL_TOL)
    flipped_tol = own.get("flipped_share", FLIPPED_SHARE_TOL)
    live_tol = own.get("relu_live_abs", RELU_LIVE_ABS_TOL)
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
        norm = lambda x: float(jnp.sqrt(jnp.sum(jnp.square(x))))  # noqa: E731
        leaf_err = {name: norm(a - b) / max(norm(b), 1e-30)
                    for name, a, b in zip(CHECKED_LEAVES, sys_leaves, ref_leaves)}
        leaf_ref = {name: norm(b) for name, b in zip(CHECKED_LEAVES, ref_leaves)}
        del sys_leaves, ref_leaves, reference, same
    got = [float(x) for x in (sys_loss, sys_norm, ref_loss, ref_norm)]
    sys_loss, sys_norm, ref_loss, ref_norm = got
    stats, ref_stats = jax.device_get(stats), jax.device_get({k: v for k, v in ref_stats.items() if k != "chosen"})
    per_expert = stats["tokens_per_expert"]
    live = [float(x) for x in stats["relu_live_share"]]
    ref_live = [float(n) / max(float(p) * c["moe_ffn_hidden_size"], 1.0)
                for n, p in zip(ref_stats["relu_live"], ref_stats["held_pairs"])]
    held, elsewhere = int(stats["held_pairs"].sum()), int(stats["elsewhere_pairs"].sum())
    first = c.get("first_expert_held", 0)
    held_sizes = [[int(x) for x in layer[first:first + c["moe_num_primary_experts"]]] for layer in per_expert]
    seq = tokens.shape[1] - 1
    plan = kernel_plan((tokens.shape[0], cfg.n_head, seq, cfg.head_dim), SlidingWindow(cfg.sliding_window), kv_heads=cfg.n_kv_head)
    out = {
        "loss_system": sys_loss, "loss_reference": ref_loss,
        "grad_norm_system": sys_norm, "grad_norm_reference": ref_norm,
        "loss_abs_err": abs(sys_loss - ref_loss),
        "grad_norm_rel_err": abs(sys_norm - ref_norm) / max(ref_norm, 1e-30),
        "leaf_grad_rel_err": leaf_err,
        "leaf_grad_norm_reference": leaf_ref,
        "expert_choices_flipped_share": flipped,
        "flipped_choices_by_layer": [int(x) for x in flipped_by_layer],
        "relu_live_abs_err": max(abs(a - b) for a, b in zip(live, ref_live)),
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
            "relu_live_share": sum(live) / len(live),
            "relu_live_share_by_layer": live,
            "relu_live_share_by_layer_reference": ref_live,
        },
        "limits": {"loss_abs_err": loss_tol, "grad_norm_rel_err": grad_tol, "leaf_grad_rel_err": leaf_tol,
                   "expert_choices_flipped_share": flipped_tol, "relu_live_abs_err": live_tol},
    }
    out["over_limit"] = sorted(
        [name for name in ("loss_abs_err", "grad_norm_rel_err", "expert_choices_flipped_share", "relu_live_abs_err")
         if not out[name] <= out["limits"][name]]
        + [f"leaf_grad_rel_err.{name}" for name, err in leaf_err.items() if not err <= leaf_tol[name]])
    out["ok"] = bool(all(map(math.isfinite, got + list(leaf_err.values()))) and not out["over_limit"]
                     and not wrong_dtype and out["routing"]["dropped"] == 0)
    return out
