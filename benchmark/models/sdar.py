"""SDAR's block-diffusion training step for the benchmark: the system under
test built through ray_tpu's public API, a plain float32 reference written
from the issue's equations, the comparison that decides `correct`, and the
arithmetic of FLOPs and bytes.

A configuration file (`benchmark/configs/<name>.json`) with `"model": "sdar"`
is served by this module. Keys read, under the names of the source's
`config.json`: `num_hidden_layers`, `hidden_size`, `num_attention_heads`,
`num_key_value_heads`, `head_dim`, `moe_intermediate_size`, `num_experts` (the
experts held here; the router's width is `published.num_experts` where the
file cuts the key), `num_experts_per_tok`, `norm_topk_prob`, `vocab_size`,
`max_position_embeddings`, `rms_norm_eps`, `rope_theta`; and the benchmark's
own: `block_length`, `mask_token_id`, `noise_eps`, `router_init_tiles`, `first_expert_held`,
`aux_loss_weight`, `dtype`, `param_dtype`, `remat_policy`, `attention`,
`learning_rate` (the peak), `warmup_steps` and `total_steps`.

A row of `batch.seq` data tokens is `2 x seq` positions in every layer (the
clean copy and the noised one); the functions below take `seq` in data tokens,
as the harness hands it, and double it themselves where positions count.
"""

from __future__ import annotations

import math
from typing import Any, Dict

from benchmark.models.lfm2 import _issued_rows, rms_norm  # noqa: F401  (the same norm and kernels)

# ------------------------------------------------------------------ arithmetic
# No jax below this line until `build`: the parent and the tests use these.
# Everything counts what this chip computes: the experts it holds, the slice of
# the vocabulary it holds, the layers it holds.


def router_width(c: Dict[str, Any]) -> int:
    """The experts the router scores: the published count where the file's
    `num_experts` is the chip's share of them."""
    return c.get("published", {}).get("num_experts", c["num_experts"])


def held_pairs_per_layer(c: Dict[str, Any], positions: int) -> float:
    """The (position, expert) pairs an even router gives the experts held here."""
    return positions * c["num_experts_per_tok"] * c["num_experts"] / router_width(c)


def attention_matmul_params(c: Dict[str, Any]) -> int:
    """One layer's W_q, W_k, W_v, W_o."""
    d, hd = c["hidden_size"], c["head_dim"]
    return 2 * d * c["num_attention_heads"] * hd + 2 * d * c["num_key_value_heads"] * hd


def num_params(c: Dict[str, Any]) -> int:
    """Every parameter this chip holds, by hand: per layer the attention's four
    matrices, the router, the held experts, two norms of hidden_size and two
    of head_dim; the embedding, the final norm and the head (untied)."""
    d = c["hidden_size"]
    per_layer = (attention_matmul_params(c) + d * router_width(c)
                 + 3 * c["num_experts"] * d * c["moe_intermediate_size"] + 2 * d + 2 * c["head_dim"])
    return 2 * c["vocab_size"] * d + d + c["num_hidden_layers"] * per_layer


def kept_pairs(c: Dict[str, Any], seq: int) -> int:
    """(query, key) pairs of one head that the mask keeps on a row of `seq`
    data tokens: with n = seq / block blocks of B, the clean copy's block-causal
    half n (n + 1) / 2, the noised copy's strictly lower half on the clean keys
    n (n - 1) / 2 and its n diagonal blocks, B^2 pairs each: (n^2 + n) B^2 =
    seq^2 + seq B; 67.1 M of the 268.4 M of the doubled row's square at 8,192 by 4."""
    n, b = seq // c["block_length"], c["block_length"]
    return (n * n + n) * b * b


def live_tile_pairs(c: Dict[str, Any], seq: int, tile_q: int, tile_k: int) -> int:
    """Tile pairs of the doubled row's score matrix that hold a kept score, counted from the issue's table and not
    from the program's schedule: of a tile's stretch in each copy the first and the last block, rows and columns."""
    b = c["block_length"]

    def stretches(lo: int, hi: int):  # (noised, first block, last block) of [lo, hi) in each copy it reaches
        return [(n, (max(lo, n * seq) - n * seq) // b, (min(hi, (n + 1) * seq) - 1 - n * seq) // b)
                for n in (0, 1) if max(lo, n * seq) < min(hi, (n + 1) * seq)]

    def live(rows, cols) -> bool:
        (q_noised, q0, q1), (k_noised, k0, k1) = rows, cols
        if k_noised:  # a noised key is seen from its own block of the noised copy alone
            return bool(q_noised) and k0 <= q1 and k1 >= q0
        return k0 <= q1 - q_noised  # a clean key from the blocks at or after it, strictly after where the query is noised

    return sum(any(live(rows, cols) for rows in stretches(r0, r0 + tile_q) for cols in stretches(c0, c0 + tile_k))
               for r0 in range(0, 2 * seq, tile_q) for c0 in range(0, 2 * seq, tile_k))


def active_matmul_params(c: Dict[str, Any]) -> float:
    """Matmul parameters a data token meets: both its copies every layer's
    attention, router and (in expectation) held experts; the noised copy alone
    the head."""
    d = c["hidden_size"]
    per_position = (attention_matmul_params(c) + d * router_width(c)
                    + held_pairs_per_layer(c, 1) * 3 * d * c["moe_intermediate_size"])
    return 2 * c["num_hidden_layers"] * per_position + c["vocab_size"] * d


def train_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    """FLOPs the model's mathematics requires per data token on this chip,
    forward and backward: 6 per active matmul parameter (both copies through
    every layer, the head over the noised half only); attention's six products
    (two forward, four backward) on the kept pairs. Recomputation is not
    counted, and a crossed tile's dropped scores neither."""
    attention = 12.0 * c["num_attention_heads"] * c["head_dim"] * kept_pairs(c, seq) / seq
    return 6.0 * active_matmul_params(c) + c["num_hidden_layers"] * attention


def flash_flops_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """FLOPs the attention of one train step requires of the two flash kernels:
    per (row, head, layer) two products forward and four backward, each 2 *
    head_dim a kept (query, key) pair."""
    return 12.0 * c["head_dim"] * kept_pairs(c, seq) * rows * c["num_attention_heads"] * c["num_hidden_layers"]


def flash_bytes_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """Bytes the two kernels must move a step over the 2 x seq positions: q, o,
    do, dq a query head, k, v, dk, dv a key/value head (bf16), the row
    statistics and delta (f32), each once: what a walk that skips the empty
    tile pairs cannot avoid, and no selection's bits."""
    positions = 2 * seq
    act, stat = positions * c["head_dim"] * 2, positions * 4
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    per_row = nh * (2 * act + stat) + nkv * 2 * act + nh * (4 * act + 2 * stat) + nkv * 4 * act
    return per_row * rows * c["num_hidden_layers"]


def moe_expert_flops_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """FLOPs the held experts of one train step require: each pair an even
    router gives them of the 2 x seq positions meets three matrices of
    hidden_size x moe_intermediate_size, 2 FLOPs a parameter forward and 4 backward."""
    pairs = held_pairs_per_layer(c, rows * 2 * seq)
    return 6.0 * 3 * c["hidden_size"] * c["moe_intermediate_size"] * pairs * c["num_hidden_layers"]


def moe_expert_bytes_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """Bytes the nine grouped products of a step must move in bf16 (as the GLM file counts them)."""
    pairs = held_pairs_per_layer(c, rows * 2 * seq)
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    one_product = pairs * d + c["num_experts"] * d * f + pairs * f
    return 2.0 * 3 * 3 * one_product * c["num_hidden_layers"]


# ---------------------------------------------------------------------- system
def model_config(c: Dict[str, Any]):
    import jax.numpy as jnp

    from ray_tpu.models.sdar import SdarConfig

    assert c["decoder_sparse_step"] == 1 and not c["mlp_only_layers"] and c["rope_scaling"] is None
    assert not c["attention_bias"] and not c["tie_word_embeddings"] and not c["use_sliding_window"]
    assert c["hidden_act"] == "silu"
    return SdarConfig(
        vocab_size=c["vocab_size"], n_layer=c["num_hidden_layers"], n_head=c["num_attention_heads"],
        n_kv_head=c["num_key_value_heads"], head_dim=c["head_dim"], d_model=c["hidden_size"],
        d_expert=c["moe_intermediate_size"], n_experts=router_width(c),
        experts_per_token=c["num_experts_per_tok"], n_experts_held=c["num_experts"],
        first_expert_held=c.get("first_expert_held", 0), norm_topk_prob=c["norm_topk_prob"],
        rope_theta=float(c["rope_theta"]), max_seq_len=c["max_position_embeddings"], norm_eps=c["rms_norm_eps"],
        aux_loss_weight=float(c["aux_loss_weight"]), block_length=c["block_length"],
        mask_token_id=c["mask_token_id"], noise_eps=float(c["noise_eps"]), dtype=jnp.dtype(c["dtype"]),
        param_dtype=jnp.dtype(c["param_dtype"]), remat_policy=c["remat_policy"], attention=c["attention"],
    )


def tile_routers(state, tiles: int):
    """`state` with every router's columns those drawn for its first `width / tiles` experts, repeated `tiles`
    times (the configuration's `router_init_tiles`, `assumed.init`): experts e, e + width / tiles, ... start with
    one column, a position's k choices are the `tiles` copies of its `k / tiles` best columns, and where `tiles`
    chips share a layer, `width / tiles` experts each, every position sends every chip `k / tiles` pairs: routing
    is even by construction, whatever the input. Where this benchmark's training starts (the moments are zero at
    step 0, so nothing else of the state changes), not a constraint, and no option of the model's: the copies'
    gradients differ with their experts."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    router = state.params["blocks"]["moe"]["router_w"]
    assert router.shape[-1] % tiles == 0
    tiled = jax.jit(lambda w: jnp.tile(w[..., :w.shape[-1] // tiles], tiles), out_shardings=router.sharding)(router)
    params = {**state.params, "blocks": {**state.params["blocks"], "moe": {
        **state.params["blocks"]["moe"], "router_w": tiled}}}
    return dataclasses.replace(state, params=params)


class System:
    """cfg, optimizer, state and jitted step, made as a user makes them; then the routers tiled where the
    configuration's initialisation says so."""

    def __init__(self, c: Dict[str, Any], mesh, seed: int):
        import jax

        from ray_tpu.models import create_train_state, default_optimizer, make_train_step

        self.c = c
        self.mesh = mesh
        self.cfg = model_config(c)
        self.optimizer = default_optimizer(
            learning_rate=c["learning_rate"], warmup_steps=c.get("warmup_steps", 0),
            total_steps=c.get("total_steps", 0))
        self.state = create_train_state(self.cfg, jax.random.PRNGKey(seed), self.optimizer, mesh=mesh)
        if c.get("router_init_tiles", 1) > 1:
            assert c["num_experts_per_tok"] % c["router_init_tiles"] == 0
            self.state = tile_routers(self.state, c["router_init_tiles"])
        self.step = make_train_step(self.cfg, self.optimizer, mesh=mesh)

    def attention_path(self, rows_per_device: int, seq: int, platform: str) -> str:
        from ray_tpu.ops.flash_attention import select_backend

        return select_backend((rows_per_device, self.cfg.n_head, 2 * seq, self.cfg.head_dim), platform)


def build(c: Dict[str, Any], mesh, seed: int) -> System:
    return System(c, mesh, seed)


# ------------------------------------------------------------------- reference
QUERY_BLOCK = 512  # queries whose (block, 2 x seq) f32 scores the reference holds at once


def dense_mask(rows, seq: int, block: int):
    """The table of the issue as a boolean (len(rows), 2 x seq): which keys of the doubled row the
    queries at positions `rows` see. r a query, c a key, blk(p) = (p mod seq) // block:

        query \\ key      clean copy               noised copy
        clean, block i   blocks <= i              none
        noised, block i  blocks < i (strict)      block i only (both directions)
    """
    import jax.numpy as jnp

    r, c = rows[:, None], jnp.arange(2 * seq)[None, :]
    q_noised, k_noised = r >= seq, c >= seq
    q_blk, k_blk = (r % seq) // block, (c % seq) // block
    on_clean = jnp.where(q_noised, k_blk < q_blk, k_blk <= q_blk)
    return jnp.where(k_noised, q_noised & (k_blk == q_blk), on_clean)


PRECISIONS = {  # name: (what one operation hands the next, what the configuration states as float32)
    "f32": ("float32", "float32"),
    "stated": (None, "float32"),  # None: the configuration's `dtype`
    "below": (None, None),
}


def reference_loss(params, tokens, noised, weight, c: Dict[str, Any], precision: str = "f32"):
    """SDAR's block-diffusion objective (as far as the source's `config.json`
    and the issue's equations say) in float32 `jax.numpy` on the clean row
    `tokens` (batch, seq), its noised copy `noised` and the loss's `weight`
    (1 / t_b where masked, 0 elsewhere; `ray_tpu.models.sdar.noise`'s draw, which
    the system is given too): (the loss, {`chosen` (layers, positions,
    experts): the experts each of the 2 x seq positions was given among all the
    router scores; `load_balance`, summed over the layers, unweighted; `ce` (batch,
    seq), each noised position's cross entropy against its own token}).

    The stack runs on the positions `[x ; x~]`, both copies at rotary positions
    0 .. seq - 1. Pre-norm block, RMSNorm eps `rms_norm_eps`, no bias: h = N(x);
    `q = rope(N_q(h W_q))`, `k = rope(N_k(h W_k))` with a norm over each head's
    own `head_dim`, `v = h W_v`, rotate-half rotary over all of a head at
    `rope_theta`; query head a on key/value head `a // group`; softmax at
    `head_dim^-1/2` over the keys `dense_mask` leaves; `x <- x + o W_o`.
    Experts: `s = softmax(N(x) W_r)` over all `router_width` experts, the
    `num_experts_per_tok` largest, renormalised; `x <- x + sum_e E_e(N(x); w_e)`
    over the experts this chip holds alone, `E_e(h; w) = W_down (w silu(W_gate h)
    W_up h)`: the partial sum goes on, as in the system. Logits at the noised
    copy's positions, each against its own
    token (no shift): `loss = (1 / seq) sum_i weight_i (-log p(x_i)) +
    aux_loss_weight x sum load_balance` (`E x sum_e f_e P_e` over the 2 x seq
    positions), a mean over the rows. No kernel, no tile schedule, no grouped
    matmul: the mask a dense boolean, a query block of `QUERY_BLOCK`
    rows and one head at a time so that the (block, 2 x seq) scores fit, every
    held expert on every position weighted by the routing matrix.

    `precision` says in what type, of the two a configuration names (`dtype`
    for activations and the operands of products, `param_dtype` for the rest):
    "f32", the reference proper, everything in float32 at `highest`; "stated",
    the configuration's own: what one operation hands the next (a product's
    operands and result, the residual stream, a norm's output, the attention's
    probabilities, SwiGLU's product) rounded to `dtype`, and in float32 what the
    configuration states so: parameters and their gradients, a product's
    accumulation, a norm's statistics, the rotation, the softmaxes, the router,
    the logits and the cross entropy; "below", the nearest precision below
    that: parameters, norms, rotation, router and logits in `dtype` too (the
    softmaxes, the accumulation inside a product and the cross entropy of those
    logits stay float32, as no implementation lowers them)."""
    import jax
    import jax.numpy as jnp

    act, stated = (jnp.dtype(t or c["dtype"]) for t in PRECISIONS[precision])
    f32 = jnp.float32
    batch, seq = tokens.shape
    positions = 2 * seq
    d, eps, k = c["hidden_size"], c["rms_norm_eps"], c["num_experts_per_tok"]
    nh, nkv, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    group, block_length = nh // nkv, c["block_length"]
    held, first, width = c["num_experts"], c.get("first_expert_held", 0), router_width(c)
    block_rows = math.gcd(positions, QUERY_BLOCK)

    def product(a, b, out=act):  # operands in `act`, accumulated in float32, handed on in `out`
        return jnp.matmul(a.astype(act), b.astype(act), preferred_element_type=f32).astype(out)

    def norm(x, scale):  # statistics in `stated`, handed on in `act`
        return rms_norm(x.astype(stated), scale.astype(stated), eps).astype(act)

    inv_freq = float(c["rope_theta"]) ** (-jnp.arange(hd // 2, dtype=f32) / (hd // 2))
    angles = jnp.tile(jnp.arange(seq, dtype=f32), 2)[:, None] * inv_freq[None]  # both copies at 0 .. seq - 1
    angles = jnp.concatenate([angles, angles], axis=-1)
    cos, sin = jnp.cos(angles).astype(stated), jnp.sin(angles).astype(stated)

    def rope(x):  # (heads, positions, head_dim)
        x = x.astype(stated)
        half = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], axis=-1)
        return (x * cos + half * sin).astype(act)

    def heads(h, w):  # (positions, d) x (d, heads, head_dim) -> (heads, positions, head_dim)
        return product(h, w.reshape(d, -1)).reshape(positions, -1, hd).transpose(1, 0, 2)

    def attention_of_row(h, layer):
        """h (positions, d) normed -> o (positions, heads * head_dim)."""
        q = rope(norm(heads(h, layer["wq"]), layer["q_norm"]))
        key = rope(norm(heads(h, layer["wk"]), layer["k_norm"]))
        v = heads(h, layer["wv"])

        @jax.checkpoint
        def query_block(start):
            kept = dense_mask(start + jnp.arange(block_rows), seq, block_length)

            def head(a):
                s = product(jax.lax.dynamic_slice_in_dim(q[a], start, block_rows), key[a // group].T, f32) / math.sqrt(hd)
                return product(jax.nn.softmax(jnp.where(kept, s, -jnp.inf), axis=-1), v[a // group])

            return jax.lax.map(jax.checkpoint(head), jnp.arange(nh))

        o = jax.lax.map(query_block, jnp.arange(0, positions, block_rows))  # (blocks, heads, rows, head_dim)
        return o.transpose(0, 2, 1, 3).reshape(positions, nh * hd)

    @jax.checkpoint
    def expert(h, weight, w_gate, w_up, w_down):
        gate, up = product(h, w_gate).astype(f32), product(h, w_up).astype(f32)
        return product(jax.nn.silu(gate) * up * weight[:, None], w_down)

    def experts(h, moe):
        h = h.reshape(batch * positions, d)
        scores = jax.nn.softmax(jnp.matmul(h.astype(stated), moe["router_w"].astype(stated)).astype(f32), axis=-1)
        chosen = jax.nn.one_hot(jax.lax.top_k(scores, k)[1], width, dtype=bool).any(axis=1)
        weights = jnp.where(chosen, scores, 0.0)
        if c["norm_topk_prob"]:
            weights = weights / weights.sum(-1, keepdims=True)
        load_balance = width * jnp.sum(chosen.sum(0) / (batch * positions) * scores.mean(0))

        def add_expert(y, xs):  # a position's held experts summed in float32, handed on once
            return y + expert(h, *xs).astype(f32), None

        y, _ = jax.lax.scan(add_expert, jnp.zeros(h.shape, f32),
                            (weights.T[first:first + held], moe["w_gate"], moe["w_up"], moe["w_down"]))
        return y.astype(act).reshape(batch, positions, d), chosen, load_balance

    @jax.checkpoint
    def block(x, layer):
        layer = jax.tree.map(lambda p: p.astype(stated), layer)
        h = norm(x, layer["attn_norm"])
        o = jnp.stack([attention_of_row(h[b], layer) for b in range(batch)])
        x = x + product(o, layer["wo"].reshape(nh * hd, d))
        y, chosen, load_balance = experts(norm(x, layer["mlp_norm"]), layer["moe"])
        return x + y, (chosen, load_balance)

    head_rows = math.gcd(seq, 2048)  # positions whose f32 logits are held at once

    @jax.checkpoint
    def head_chunk(x, targets, scale, table):
        log_p = jax.nn.log_softmax(product(norm(x, scale), table.T, stated).astype(f32), axis=-1)
        return -jnp.take_along_axis(log_p, targets[..., None], axis=-1)[..., 0]  # (batch, head_rows)

    def head(x, scale, table):
        chunks = lambda a: jnp.moveaxis(a.reshape(batch, seq // head_rows, head_rows, *a.shape[2:]), 1, 0)
        _, ce = jax.lax.scan(lambda _, xs: (None, head_chunk(*xs, scale, table)), None, (chunks(x), chunks(tokens)))
        ce = jnp.moveaxis(ce, 0, 1).reshape(batch, seq)  # each noised position's, against its own token
        return (weight * ce).sum() / (batch * seq), ce

    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(act)[jnp.concatenate([tokens, noised], axis=1)]
        # A scan, so that the backward pass makes one layer's forward again at a time (as the Keye file's).
        x, (chosen, load_balance) = jax.lax.scan(block, x, params["blocks"])
        weighted, ce = head(x[:, seq:], params["final_norm"].astype(stated), params["lm_head"].astype(stated))
    loss = weighted + c["aux_loss_weight"] * load_balance.sum()
    return loss, {"chosen": chosen, "load_balance": load_balance.sum(), "ce": jax.lax.stop_gradient(ce)}


# Tolerances of the agreement between the system (bf16 activations and matmul
# operands, the Pallas kernels under the mask by structure, grouped matmuls
# over the held groups; f32 router, norms, logits and parameters) and the
# reference, at seeded initial weights and one draw of the noise (`check`
# makes it and hands all the same), on the row (8,192 data tokens, 16,384
# positions) of the run's first batch. Every reading below is `check`'s own, on
# the chip at the published widths, with the named side in the program's place
# (`tools/sdar_readings.py` over six seeds, and the cell's own check in seven runs of `fed8k`; my chip runs, PR 47,
# PERF.md section 6).
#
# Against the reference at the stated precision, element by element: the shared
# rounding of the bf16 operands is on both sides there, and what is left is
# what a side does otherwise.
#   cross entropies, mean |difference| over the 8,192 noised positions
#       the system                          1.19e-3 .. 1.41e-3 (13 readings; 1.19 .. 1.22 but where a layer's masked positions
#                                           stand near a tie of two experts: 1.34, 1.41 with 0.8 % of choices flipped)
#       the reference a precision below     2.37e-3 .. 2.73e-3 (6)  (parameters, norms, rotation, router, logits in bf16)
#     limit 1.9e-3: a third above the system's largest, a fifth under the least
#     of the precision below, which therefore comes out not `ok` on every seed,
#     by this limit. The system's floor of 1.19e-3 is the kernels' own order of
#     rounding (the scaled q rounded once more, the probabilities rounded
#     before their sum is divided out) and the router's product at the
#     device's default precision; it moves with the seed only through the
#     experts' near-ties (each such layer adds 0.1e-3 to 0.2e-3: the room above).
#   W_q's and W_k's gradients, |difference| / |reference|
#       the system                          9.89e-3 .. 1.08e-2 (13)
#       the precision below                 1.21e-2 .. 1.30e-2 (6)  (passes this one: "by one limit, not by each")
#       one tile pair dropped from both schedules     4.88e-2 .. 5.23e-2 (6)
#       the strict quadrant read as the other         0.229 .. 0.247 (2)
#       blocks of 8 for 4                             0.389 .. 0.411 (2)
#     limit 2.2e-2: twice the system's largest, under half of the least planted
#     fault's. These gradients reach the loss through the masked scores alone:
#     at seeded weights attention is a hundredth of the residual, and the loss
#     and the whole gradient's norm pass the mask by (blocks of 8 move them by
#     1e-3 and 2e-3, inside their limits below).
#
# Against the float32 reference, four scalars that hold another objective out
# and not another precision (the system 21 readings, the precision below 6+):
#   loss              system 2.4e-5..1.11e-3; below 2.0e-5..1.73e-3
#   gradient norm     system 2.8e-6..1.41e-3; below 1.9e-5..4.81e-3
#   W_q, W_k gradient norm   system 1.0e-6..1.29e-3; below 1.8e-4..4.49e-3
#   flipped choices   system 0.31..0.89 % of the 655,360 (position, slot) choices; below 0.43..0.54 % (the reference's own choices, an earlier form of the tool)
# A quarter of the positions carry one embedding, the mask token's, and the
# head reads the same logits at all of them: what rounding does to that one row
# it does to 4,096 cross entropies alike, so these move by one draw of rounding
# a seed, for either side. Their limits stand two to three times above every
# reading: a plain mean for the 1 / t-weighted sum, another draw, or the causal
# mask moves one of them by 3e-3 to 3e-1 (`tests/test_sdar.py`). Position by
# position against float32 the system reads 4.37e-3..4.72e-3 and the precision
# below 4.76e-3..5.01e-3, 8 % apart in the median: printed (`ce_abs_err_mean`), no limit.
# Parameters kept in bf16 are also seen by name (`state_dtypes_other_than_stated`).
CE_STATED_ABS_MEAN_TOL = 1.9e-3
QK_GRAD_STATED_REL_TOL = 2.2e-2
LOSS_ABS_TOL = 3e-3
GRAD_NORM_REL_TOL = 4e-3
QK_GRAD_NORM_REL_TOL = 4e-3
FLIPPED_SHARE_TOL = 1.8e-2
LIMITS = {  # name in a configuration's `check_tolerances`: (the reading it bounds, this file's limit)
    "ce_stated_abs_mean": ("ce_abs_err_mean_stated", CE_STATED_ABS_MEAN_TOL),
    "qk_grad_stated_rel": ("qk_grad_rel_dist_stated", QK_GRAD_STATED_REL_TOL),
    "loss_abs": ("loss_abs_err", LOSS_ABS_TOL),
    "grad_norm_rel": ("grad_norm_rel_err", GRAD_NORM_REL_TOL),
    "qk_grad_norm_rel": ("qk_grad_norm_rel_err", QK_GRAD_NORM_REL_TOL),
    "flipped_share": ("expert_choices_flipped_share", FLIPPED_SHARE_TOL),
}


def draw_faults(c: Dict[str, Any], row, noised, masked, weight) -> list:
    """What of `noise`'s draw on `row` (rows, seq; numpy) is not the objective's, by name (empty: nothing). The
    reference is handed the program's own draw, so the draw is held to its definition here: a masked token is the
    mask token's id and no other token changed; a weight is 1 / t_b where masked and 0 elsewhere, one t_b a block,
    in [noise_eps, 1]; and over the row the masked share is E[t] = (1 + eps) / 2 and the mean weight 1 (a token is
    masked with probability t at weight 1 / t), each within 6 standard deviations of a row of that many blocks."""
    import numpy as np

    b, eps = c["block_length"], float(c["noise_eps"])
    faults = []
    if not np.array_equal(noised, np.where(masked, c["mask_token_id"], row)):
        faults.append("noised ids are not the row with the mask token where masked")
    if np.any(weight[~masked] != 0) or np.any(weight[masked] <= 0):
        faults.append("weights are not positive where masked and 0 elsewhere")
    blocks_w, blocks_m = weight.reshape(-1, b), masked.reshape(-1, b)
    per_block = np.where(blocks_m, blocks_w, 0).max(axis=1, keepdims=True)  # 1 / t_b of a block with a masked token
    if np.any(np.where(blocks_m, blocks_w != per_block, False)):
        faults.append("a block's masked tokens carry different weights")
    seen = per_block[per_block > 0]
    if seen.size and (seen.max() > (1 / eps) * (1 + 1e-6) or seen.min() < 1 - 1e-6):
        faults.append("a weight is not 1 / t for a t in [noise_eps, 1]")
    n = blocks_m.shape[0]
    share_sd = math.sqrt(n * (b / 6 + b * b / 12)) / (n * b)  # a block's count: E var + var E over t ~ U(0, 1)
    if abs(masked.mean() - (1 + eps) / 2) > 6 * share_sd:
        faults.append(f"masked share {masked.mean():.4f} is not (1 + eps) / 2 within {6 * share_sd:.4f}")
    weight_sd = math.sqrt((math.log(1 / eps) / (1 - eps) - 1) / (n * b))  # var of a token's weight: E[1 / t] - 1
    if abs(weight.mean() - 1) > 6 * weight_sd:
        faults.append(f"mean weight {weight.mean():.4f} is not 1 within {6 * weight_sd:.4f}")
    return faults


def system_program(system: System):
    """The system's side of `check`: (params, tokens (rows, seq + 1), key) -> (loss, gradients, `ce` (rows, seq):
    each noised position's cross entropy against its own token) through `loss_and_parts`: the draw, the two
    copies, the attention kernels under the mask and the held-experts layer."""
    import jax

    from ray_tpu.models import sdar as model

    def program(params, tokens, key):
        (loss, parts), grads = jax.value_and_grad(lambda p: model.loss_and_parts(
            p, {"tokens": tokens}, system.cfg, step_rng=key, mesh=system.mesh), has_aux=True)(params)
        return loss, grads, parts["ce"]

    return program


def reference_program(c: Dict[str, Any], precision: str):
    """The reference in the system's place (`check(program=)`): the same signature, the draw made as the system
    makes it. `tools/sdar_readings.py` hands `check` the precision below the stated one this way."""
    import jax

    from ray_tpu.models import sdar as model

    def program(params, tokens, key):
        noised, _, weight = model.noise(tokens[:, :-1], key, model_config(c))
        (loss, aux), grads = jax.value_and_grad(lambda p: reference_loss(
            p, tokens[:, :-1], noised, weight, c, precision), has_aux=True)(params)
        return loss, grads, aux["ce"]

    return program


def check(system: System, tokens, *, program=None, **limits: float) -> Dict[str, Any]:
    """The system's `loss_fn` (`system_program`; `program` puts another in its
    place, for the readings a limit lies between) against the reference, on
    `tokens` (a jax array, already placed, (rows, seq + 1) as the loop hands
    them) with the run's own parameters and one draw of the noise that all are
    given. Against the reference at the precision the configuration states,
    element by element: each noised position's cross entropy, and the gradients
    of W_q and W_k, which reach the loss through the masked scores and nothing
    else (`qk_grad_rel_dist_stated`: the norm of the difference over the
    reference's norm). Against the float32 reference: the loss, the global
    gradient norm, W_q's and W_k's gradients' norm, and the share of (position,
    slot) choices on which system and reference pick different experts. Beside
    them what the routers did with the doubled row (`dropped` must be 0), the
    draw held to its definition (`draw_faults`), and of the mask the tile pairs
    the kernels' schedule walks over those that hold a kept score (counted from
    the table, `live_tile_pairs`). Three programs, one after the other, so that
    no two whole gradient trees are held at once. A limit (`LIMITS`' names) not
    given is the configuration's own (`check_tolerances`: the rehearsal's toy),
    else this file's, which are the published widths'."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import sdar as model
    from ray_tpu.ops.flash_attention import BlockDiffusion, kernel_plan

    cfg, c = system.cfg, system.c
    assert set(limits) <= set(LIMITS), sorted(set(limits) - set(LIMITS))
    limits = {name: limits.get(name, c.get("check_tolerances", {}).get(name, default))
              for name, (_, default) in LIMITS.items()}
    program = program or system_program(system)
    params = system.state.params
    key = jax.random.PRNGKey(0)  # the draw all are given: `loss_fn` makes it from the key, the references get its result
    through_scores = lambda grads: {"wq": grads["blocks"]["wq"], "wk": grads["blocks"]["wk"]}
    norm = lambda tree: optax.global_norm(tree)
    distance = lambda a, b: norm(jax.tree.map(jnp.subtract, a, b))

    def of_system(params, tokens):
        loss, grads, ce = program(params, tokens, key)
        row = tokens[:, :-1]
        noised, masked, weight = model.noise(row, key, cfg)
        return (loss, norm(grads), through_scores(grads), ce, model.routing_stats(params, row, noised, cfg),
                {"noised": noised, "masked": masked, "weight": weight})

    def of_reference(params, tokens, draw, experts):
        (loss, aux), grads = jax.value_and_grad(
            lambda p: reference_loss(p, tokens[:, :-1], draw["noised"], draw["weight"], c), has_aux=True)(params)
        # experts (layers, positions, k): is each of the system's choices one of the reference's?
        same = jnp.take_along_axis(aux.pop("chosen"), experts, axis=-1)
        return loss, norm(grads), norm(through_scores(grads)), aux.pop("ce"), 1.0 - same.mean(), aux

    def of_stated(params, tokens, draw, sys_qk, sys_ce):
        (_, aux), grads = jax.value_and_grad(lambda p: reference_loss(
            p, tokens[:, :-1], draw["noised"], draw["weight"], c, "stated"), has_aux=True)(params)
        qk = through_scores(grads)
        return jnp.abs(sys_ce - aux["ce"]).mean(), distance(sys_qk, qk) / norm(qk)

    sys_loss, sys_norm, sys_qk, sys_ce, routing, draw = jax.jit(of_system)(params, tokens)
    ce_stated, qk_stated = jax.jit(of_stated)(params, tokens, draw, sys_qk, sys_ce)
    sys_qk = norm(sys_qk)
    ref_loss, ref_norm, ref_qk, ref_ce, flipped, ref = jax.jit(of_reference)(
        params, tokens, draw, routing.pop("experts"))
    ce_err = jnp.abs(sys_ce - ref_ce)  # (rows, seq): position by position
    got = [float(x) for x in (sys_loss, sys_norm, sys_qk, ref_loss, ref_norm, ref_qk, ce_err.mean(), ce_err.max(),
                              ce_stated, qk_stated)]
    sys_loss, sys_norm, sys_qk, ref_loss, ref_norm, ref_qk, ce_err_mean, ce_err_max, ce_stated, qk_stated = got
    want_dtype = jnp.dtype(c["param_dtype"])
    leaves = jax.tree.leaves(params) + [
        x for x in jax.tree.leaves(system.state.opt_state) if getattr(x, "ndim", 0) > 0]
    wrong_dtype = sorted({str(x.dtype) for x in leaves if x.dtype != want_dtype})
    routing, ref, draw, row = jax.device_get((routing, ref, draw, tokens[:, :-1]))
    masked, weight = draw["masked"], draw["weight"]
    per_expert = routing["tokens_per_expert"]
    held, elsewhere = int(routing["held_pairs"].sum()), int(routing["elsewhere_pairs"].sum())
    first = c.get("first_expert_held", 0)
    held_sizes = [[int(x) for x in layer[first:first + c["num_experts"]]] for layer in per_expert]
    seq = tokens.shape[1] - 1
    plan = kernel_plan((tokens.shape[0], cfg.n_head, 2 * seq, cfg.head_dim), BlockDiffusion(seq, cfg.block_length),
                       kv_heads=cfg.n_kv_head)
    live = live_tile_pairs(c, seq, plan.tile_q, plan.tile_k)
    # The forward kernel may halve or quarter the plan's Q tile (`_fwd_pairs_plan`): the live pairs at each, by the
    # number of tile pairs of the square, which is what a traced kernel's `tiles_<walked>of<all>` names its tiles by.
    q_tiles = [plan.tile_q // n for n in (1, 2, 4) if plan.tile_q % n == 0]
    live_of_all = {str((2 * seq // tile_q) * (2 * seq // plan.tile_k)): live_tile_pairs(c, seq, tile_q, plan.tile_k)
                   for tile_q in q_tiles}
    out = {
        "loss_system": sys_loss, "loss_reference": ref_loss,
        "grad_norm_system": sys_norm, "grad_norm_reference": ref_norm,
        "ce_abs_err_mean_stated": ce_stated, "qk_grad_rel_dist_stated": qk_stated,
        "loss_abs_err": abs(sys_loss - ref_loss),
        "ce_abs_err_mean": ce_err_mean, "ce_abs_err_max": ce_err_max,
        "grad_norm_rel_err": abs(sys_norm - ref_norm) / max(ref_norm, 1e-30),
        "qk_grad_norm_system": sys_qk, "qk_grad_norm_reference": ref_qk,
        "qk_grad_norm_rel_err": abs(sys_qk - ref_qk) / max(ref_qk, 1e-30),
        "load_balance_reference": float(ref["load_balance"]),
        "expert_choices_flipped_share": float(flipped),
        "state_dtypes_other_than_stated": wrong_dtype,
        "draw_faults": draw_faults(c, row, draw["noised"], masked, weight),
        "block_diffusion": {
            "masked_share": float(masked.mean()),
            "weight_mean": float(weight.mean()),  # 1 in expectation: a token is masked with probability t at weight 1 / t
            "weight_max": float(weight.max()),
            "kept_pairs_per_head": kept_pairs(c, seq),
            "tiles": [plan.tile_q, plan.tile_k],
            "walked_tiles": plan.tiles_visited, "crossed_tiles": plan.tiles_masked, "live_tiles": live,
            "all_tiles": plan.tiles_total, "live_tiles_of_all": live_of_all,
            "walked_over_live_tiles": plan.tiles_visited / max(live, 1),
        },
        "routing": {
            "pairs_per_layer": int(per_expert[0].sum()),
            "held_pairs": held,
            "elsewhere_pairs": elsewhere,
            "held_pairs_share": held / max(held + elsewhere, 1),
            "held_pairs_per_layer": [int(x) for x in routing["held_pairs"]],
            "held_tokens_per_expert": held_sizes,
            "issued_over_held": _issued_rows(held_sizes) / max(9 * held, 1),
            "dropped": int(routing["dropped"].sum()),
            "compact_layers": int(routing["compact"].sum()),
            "load_max_over_mean": float(routing["load_max_over_mean"].max()),
            "load_max_over_mean_by_layer": [float(x) for x in routing["load_max_over_mean"]],
            "tokens_per_expert_min": int(per_expert.min()),
            "tokens_per_expert_max": int(per_expert.max()),
        },
    }
    out["over_limit"] = sorted(name for name, (reading, _) in LIMITS.items() if not out[reading] <= limits[name])
    out["ok"] = bool(all(map(math.isfinite, got)) and not out["over_limit"] and not wrong_dtype
                     and not out["draw_faults"] and out["routing"]["dropped"] == 0)
    return out
