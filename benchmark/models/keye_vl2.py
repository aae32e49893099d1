"""Keye-VL-2.0's language model for the benchmark: the system under test built
through ray_tpu's public API, a plain float32 reference written from the
issue's equations, the comparison that decides `correct`, and the arithmetic
of FLOPs and bytes.

A configuration file (`benchmark/configs/<name>.json`) with `"model":
"keye_vl2"` is served by this module. Keys read, under the names of the
source's `config.json`: `num_hidden_layers`, `hidden_size`,
`num_attention_heads`, `num_key_value_heads`, `head_dim`,
`moe_intermediate_size`, `num_experts` (the experts held here; the router's
width is `published.num_experts` where the file cuts the key),
`num_experts_per_tok`, `norm_topk_prob`, `vocab_size`,
`max_position_embeddings`, `rms_norm_eps`, `rope_theta`,
`rope_scaling.mrope_section`, `sa_config` (`indexer_num_heads`,
`indexer_head_dim`, `indexer_num_kv_heads` = 1, `topk`; `q_chunk_size` and
`kv_chunk_size` are the source's tiling and enter no equation); and the
benchmark's own: `first_expert_held`, `aux_loss_weight`, `index_loss_weight`,
`dtype`, `param_dtype`, `remat_policy`, `attention`, `learning_rate` (the
peak), `warmup_steps` and `total_steps`.
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


def held_pairs_per_layer(c: Dict[str, Any], tokens: int) -> float:
    """The (token, expert) pairs an even router gives the experts held here."""
    return tokens * c["num_experts_per_tok"] * c["num_experts"] / router_width(c)


def attention_matmul_params(c: Dict[str, Any]) -> int:
    """One layer's W_q, W_k, W_v, W_o and the indexer's three matrices."""
    d, hd, sa = c["hidden_size"], c["head_dim"], c["sa_config"]
    return (2 * d * c["num_attention_heads"] * hd + 2 * d * c["num_key_value_heads"] * hd
            + d * sa["indexer_num_heads"] * (sa["indexer_head_dim"] + 1) + d * sa["indexer_head_dim"])


def num_params(c: Dict[str, Any]) -> int:
    """Every parameter this chip holds, by hand: per layer the attention's four
    matrices, the indexer's three, the router, the held experts, two norms of
    hidden_size, two of head_dim and the indexer's LayerNorm (scale and bias);
    the embedding, the final norm and the head (untied)."""
    d = c["hidden_size"]
    per_layer = (attention_matmul_params(c) + d * router_width(c)
                 + 3 * c["num_experts"] * d * c["moe_intermediate_size"]
                 + 2 * d + 2 * c["head_dim"] + 2 * c["sa_config"]["indexer_head_dim"])
    return 2 * c["vocab_size"] * d + d + c["num_hidden_layers"] * per_layer


def selected_pairs(c: Dict[str, Any], seq: int) -> int:
    """(query, key) pairs of one head of one row that the selection keeps, ties
    apart: the causal half up to `topk` keys a query, `topk` a query from there
    on: 31.46 M of the 134.2 M causal pairs of a row of 16,384 at 2,048."""
    k = min(c["sa_config"]["topk"], seq)
    return k * (k + 1) // 2 + (seq - k) * k


def active_matmul_params(c: Dict[str, Any]) -> float:
    d = c["hidden_size"]
    per_layer = (attention_matmul_params(c) + d * router_width(c)
                 + held_pairs_per_layer(c, 1) * 3 * d * c["moe_intermediate_size"])
    return c["num_hidden_layers"] * per_layer + c["vocab_size"] * d


def train_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    """FLOPs the model's mathematics requires per token on this chip, forward
    and backward: 6 per active matmul parameter; attention's six products (two
    forward, four backward) and the indexer loss's one more pass of q . k on
    the selected pairs alone; the indexer's scores (one product forward, two
    backward) on the causal half. The kernels walk every causal pair to reach
    the selected ones, 4.3 times as many: that walk is not the model's, and
    `step.mfu_pct` does not credit it. Recomputation is not counted."""
    sa = c["sa_config"]
    pairs = selected_pairs(c, seq) / seq
    attention = 14.0 * c["num_attention_heads"] * c["head_dim"] * pairs
    indexer = 6.0 * sa["indexer_num_heads"] * sa["indexer_head_dim"] * (seq + 1) / 2
    return 6.0 * active_matmul_params(c) + c["num_hidden_layers"] * (attention + indexer)


def flash_flops_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """FLOPs the attention of one train step requires of the two flash kernels:
    per (row, head, layer) two products forward and four backward, each 2 *
    head_dim a selected (query, key) pair. What the mathematics asks for, as
    `kernels.gmm_roofline`'s floor is what the routing asks for: a kernel that
    computes pairs the selection drops reads a lower share for it, never more
    than 100 %."""
    return (12.0 * c["head_dim"] * selected_pairs(c, seq)
            * rows * c["num_attention_heads"] * c["num_hidden_layers"])


def flash_bytes_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """Bytes the two kernels must move a step: q, o, do, dq a query head, k, v,
    dk, dv a key/value head (bf16), the row statistics and delta (f32), and the
    selection's bits once each way."""
    act, stat = seq * c["head_dim"] * 2, seq * 4
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    per_row = nh * (2 * act + stat) + nkv * 2 * act + nh * (4 * act + 2 * stat) + nkv * 4 * act + 2 * seq * seq / 8
    return per_row * rows * c["num_hidden_layers"]


def select_flops_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """The `select` kernel's products: the indexer's scores on the causal half,
    2 * heads * dim a pair, forward only (the selection has no gradient)."""
    sa = c["sa_config"]
    return 2.0 * sa["indexer_num_heads"] * sa["indexer_head_dim"] * seq * (seq + 1) / 2 * rows * c["num_hidden_layers"]


def select_bytes_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """qI, kI (bf16), w (f32) in; the selection's bits and a row statistic out."""
    sa = c["sa_config"]
    per_row = seq * sa["indexer_num_heads"] * (sa["indexer_head_dim"] * 2 + 4) + seq * sa["indexer_head_dim"] * 2
    return (per_row + seq * seq / 8 + seq * 4) * rows * c["num_hidden_layers"]


def index_loss_flops_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """The `index_loss` kernel's products: q . k of every head on the selected
    pairs (the probabilities), and on the same pairs the indexer's scores made
    again and their two gradient products: 2 * heads * head_dim + 3 * 2 *
    indexer heads * indexer dim a pair."""
    sa = c["sa_config"]
    per_pair = 2.0 * c["num_attention_heads"] * c["head_dim"] + 6.0 * sa["indexer_num_heads"] * sa["indexer_head_dim"]
    return per_pair * selected_pairs(c, seq) * rows * c["num_hidden_layers"]


def index_loss_bytes_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """q, k and the row statistics in; qI, kI, w in and their gradients out; the selection's bits."""
    sa = c["sa_config"]
    act = seq * c["head_dim"] * 2
    index = seq * sa["indexer_num_heads"] * (sa["indexer_head_dim"] * 2 + 4) + seq * sa["indexer_head_dim"] * 2
    per_row = (c["num_attention_heads"] * (act + seq * 4) + c["num_key_value_heads"] * act
               + 2 * index + seq * seq / 8)
    return per_row * rows * c["num_hidden_layers"]


def moe_expert_flops_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """FLOPs the held experts of one train step require: each pair an even
    router gives them meets three matrices of hidden_size x
    moe_intermediate_size, 2 FLOPs a parameter forward and 4 backward."""
    pairs = held_pairs_per_layer(c, rows * seq)
    return 6.0 * 3 * c["hidden_size"] * c["moe_intermediate_size"] * pairs * c["num_hidden_layers"]


def moe_expert_bytes_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """Bytes the nine grouped products of a step must move in bf16 (as the GLM file counts them)."""
    pairs = held_pairs_per_layer(c, rows * seq)
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    one_product = pairs * d + c["num_experts"] * d * f + pairs * f
    return 2.0 * 3 * 3 * one_product * c["num_hidden_layers"]


# ---------------------------------------------------------------------- system
def model_config(c: Dict[str, Any]):
    import jax.numpy as jnp

    from ray_tpu.models.keye_vl2 import KeyeVL2Config

    sa = c["sa_config"]
    assert sa["indexer_num_kv_heads"] == 1 and c["decoder_sparse_step"] == 1 and not c["mlp_only_layers"]
    assert not c["attention_bias"] and not c["tie_word_embeddings"] and not c["use_sliding_window"]
    assert c["hidden_act"] == "silu" and c["rope_scaling"]["rope_type"] == "default"
    return KeyeVL2Config(
        vocab_size=c["vocab_size"], n_layer=c["num_hidden_layers"], n_head=c["num_attention_heads"],
        n_kv_head=c["num_key_value_heads"], head_dim=c["head_dim"], d_model=c["hidden_size"],
        d_expert=c["moe_intermediate_size"], n_experts=router_width(c),
        experts_per_token=c["num_experts_per_tok"], n_experts_held=c["num_experts"],
        first_expert_held=c.get("first_expert_held", 0), norm_topk_prob=c["norm_topk_prob"],
        index_n_heads=sa["indexer_num_heads"], index_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"],
        index_loss_weight=float(c["index_loss_weight"]), mrope_section=tuple(c["rope_scaling"]["mrope_section"]),
        rope_theta=float(c["rope_theta"]), max_seq_len=c["max_position_embeddings"], norm_eps=c["rms_norm_eps"],
        aux_loss_weight=float(c["aux_loss_weight"]), dtype=jnp.dtype(c["dtype"]),
        param_dtype=jnp.dtype(c["param_dtype"]), remat_policy=c["remat_policy"], attention=c["attention"],
    )


class System:
    """cfg, optimizer, state and jitted step, made as a user makes them."""

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
        self.step = make_train_step(self.cfg, self.optimizer, mesh=mesh)

    def attention_path(self, rows_per_device: int, seq: int, platform: str) -> str:
        from ray_tpu.ops.flash_attention import select_backend

        return select_backend((rows_per_device, self.cfg.n_head, seq, self.cfg.head_dim), platform)


def build(c: Dict[str, Any], mesh, seed: int) -> System:
    return System(c, mesh, seed)


# ------------------------------------------------------------------- reference
QUERY_BLOCK = 512  # queries whose (block, seq) f32 scores the reference holds at once


def reference_loss(params, tokens, c: Dict[str, Any], dtype=None, system_keep=None, positions=None,
                   return_keep: bool = False):
    """Keye-VL-2.0's language model (as far as the source's `config.json` and
    the issue's equations say) in float32 `jax.numpy` on `tokens` (batch, seq +
    1): (the loss, {`chosen` (layers, tokens, experts): the experts each token
    was given among all the router scores; `index_loss`: L_I summed over the
    layers; `load_balance`, the same of that term, unweighted;
    `selected_pairs` and `selection_differs` (layers,): the (query, key) pairs
    the reference selects, and those on which `system_keep` (layers, batch, seq,
    words; `flash_attention.pack_keep`'s form) differs from it}).

    Pre-norm block, RMSNorm eps `rms_norm_eps`, no bias but the indexer's
    LayerNorm: h = N(x). Indexer on stop_gradient(h): `qI = rope(h W_Iq)` in
    `indexer_num_heads` heads, `kI = rope(LN(h W_Ik))`, `w = (h W_Iw) / sqrt(heads x
    dim)`, `I_ts = sum_j w_tj relu(qI_tj . kI_s)`; rotary on all of an indexer
    head's dimensions by the first position component. Selection: `tau_t` the
    `topk`-th largest of `{I_ts : s <= t}` (`lax.top_k`), `S_t = {s <= t : I_ts
    >= tau_t}`, every `s <= t` where `t < topk`; no gradient. Attention: `q =
    rope(N_q(h W_q))`, `k = rope(N_k(h W_k))` with a norm over each head's own
    `head_dim`, `v = h W_v`, query head a on key/value head `a // group`,
    softmax over `S_t` at `head_dim^-1/2`, `x <- x + o W_o`. Experts: `s =
    softmax(N(x) W_r)` over all `router_width` experts, the
    `num_experts_per_tok` largest, renormalised; `x <- x + sum_e w_e E_e(N(x))`
    over the experts this chip holds alone: the partial sum goes on, as in the
    system. Indexer loss: `P = mean over heads of the attention's
    probabilities`, no gradient; `L_I = mean_t sum_{s in S_t} P_ts (log P_ts -
    log softmax_{S_t}(I_t.)_s)`. `loss = CE + index_loss_weight x sum L_I +
    aux_loss_weight x sum load_balance` (`E x sum_e f_e P_e`). `positions` (3,
    batch, seq) are the three rotary components, a head's `head_dim / 2`
    frequency pairs turning in runs of `mrope_section` by the first, second,
    third; None is text's, the three equal to the position. No kernel, no packed selection,
    no grouped matmul, no bf16: dense masks, a query block of `QUERY_BLOCK` rows
    and one head at a time so that the (block, seq) scores fit, every held
    expert on every token weighted by the routing matrix.

    `dtype` (default float32) computes everything up to and including the
    logits and the indexer's scores in that type instead, the cross entropy in
    float32 as always: what a lower precision than the configuration states
    would give, for PERF.md's second reading (`tools/keye_readings.py`), for
    which `return_keep` adds `keep`, the reference's own selections in the
    packed form `system_keep` comes in.
    """
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.flash_attention import pack_keep, unpack_keep

    f = jnp.dtype(dtype or jnp.float32)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    batch, seq = inputs.shape
    d, eps, k = c["hidden_size"], c["rms_norm_eps"], c["num_experts_per_tok"]
    nh, nkv, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    group, sa = nh // nkv, c["sa_config"]
    hi, di, topk = sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]
    held, first, width = c["num_experts"], c.get("first_expert_held", 0), router_width(c)
    block_rows = math.gcd(seq, QUERY_BLOCK)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(seq), (3, batch, seq))
    sections = c["rope_scaling"]["mrope_section"]
    component_of_pair = [n for n, run in enumerate(sections) for _ in range(run)]

    def rotary(pairs: int, components, b: int):
        """Row b's rotation of (..., seq, 2 * pairs): pair p turns by component `components[p]`."""
        inv_freq = float(c["rope_theta"]) ** (-jnp.arange(pairs, dtype=jnp.float32) / pairs)
        by_pair = jnp.stack([positions[n, b] for n in components], axis=-1).astype(jnp.float32)  # (seq, pairs)
        angles = by_pair * inv_freq[None]
        angles = jnp.concatenate([angles, angles], axis=-1)
        cos, sin = jnp.cos(angles).astype(f), jnp.sin(angles).astype(f)

        def rotated(x):
            half = jnp.concatenate([-x[..., pairs:], x[..., :pairs]], axis=-1)
            return x * cos + half * sin
        return rotated

    def layer_norm(x, scale, bias):
        mean = x.mean(-1, keepdims=True)
        return (x - mean) / jnp.sqrt(((x - mean) ** 2).mean(-1, keepdims=True) + eps) * scale + bias

    def attention_of_row(b, h, layer, kept_by_system):
        """Row b, h (seq, d) normed: (o (seq, heads * head_dim), L_I of the
        row as a sum over queries, pairs selected, pairs that differ)."""
        ix = layer["indexer"]
        rope, rope_i = rotary(hd // 2, component_of_pair, b), rotary(di // 2, [0] * (di // 2), b)
        q = rope(rms_norm(jnp.einsum("sd,dnh->nsh", h, layer["wq"]), layer["q_norm"], eps))
        key = rope(rms_norm(jnp.einsum("sd,dnh->nsh", h, layer["wk"]), layer["k_norm"], eps))
        v = jnp.einsum("sd,dnh->nsh", h, layer["wv"])
        hs = jax.lax.stop_gradient(h)
        q_i = rope_i(jnp.einsum("sd,dnh->nsh", hs, ix["wq"]))
        k_i = rope_i(layer_norm(hs @ ix["wk"], ix["k_norm"], ix["k_norm_bias"]))
        w = (hs @ ix["ww"]) / jnp.sqrt(jnp.asarray(hi * di, f))

        @jax.checkpoint
        def query_block(start):
            rows = start + jnp.arange(block_rows)
            causal = rows[:, None] >= jnp.arange(seq)[None, :]
            rows_of = lambda x, axis: jax.lax.dynamic_slice_in_dim(x, start, block_rows, axis=axis)
            s_i = jnp.einsum("nqh,kh->nqk", rows_of(q_i, 1), k_i)
            scores = jnp.einsum("nqk,qn->qk", jax.nn.relu(s_i), rows_of(w, 0)).astype(jnp.float32)
            masked = jnp.where(causal, jax.lax.stop_gradient(scores), -jnp.inf)
            tau = jax.lax.top_k(masked, min(topk, seq))[0][:, -1:]
            kept = (masked >= tau) & causal

            def probabilities(a):
                s = rows_of(q[a], 0) @ key[a // group].T / jnp.sqrt(jnp.asarray(hd, f))
                return jax.nn.softmax(jnp.where(kept, s.astype(jnp.float32), -jnp.inf), axis=-1)

            o = jax.lax.map(jax.checkpoint(lambda a: probabilities(a).astype(f) @ v[a // group]), jnp.arange(nh))
            p = jax.lax.stop_gradient(jax.lax.fori_loop(
                0, nh, lambda a, total: total + probabilities(a), jnp.zeros((block_rows, seq), jnp.float32)) / nh)
            log_q = jax.nn.log_softmax(jnp.where(kept, scores, -jnp.inf), axis=-1)
            positive = kept & (p > 0)
            kl = jnp.sum(jnp.where(positive, p * (jnp.log(jnp.where(positive, p, 1.0)) - jnp.where(kept, log_q, 0.0)), 0.0))
            differs = jnp.zeros((), jnp.int32)
            if kept_by_system is not None:
                theirs = unpack_keep(rows_of(kept_by_system, 0), seq)
                differs = jnp.sum(kept != theirs, dtype=jnp.int32)
            return o, kl, jnp.sum(kept, dtype=jnp.float32), differs, pack_keep(kept) if return_keep else None

        o, kl, n_kept, differs, keep = jax.lax.map(query_block, jnp.arange(0, seq, block_rows))
        # (blocks, heads, rows, head_dim) -> (seq, heads * head_dim)
        o = o.transpose(0, 2, 1, 3).reshape(seq, nh * hd)
        return o, kl.sum(), n_kept.sum(), differs.sum(), keep.reshape(seq, -1) if return_keep else None

    @jax.checkpoint
    def expert(h, weight, w_gate, w_up, w_down):
        return weight[:, None] * ((jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down)

    def experts(h, moe):
        h = h.reshape(batch * seq, d)
        scores = jax.nn.softmax((h @ moe["router_w"]).astype(jnp.float32), axis=-1)
        chosen = jax.nn.one_hot(jax.lax.top_k(scores, k)[1], width, dtype=bool).any(axis=1)
        weights = jnp.where(chosen, scores, 0.0)
        if c["norm_topk_prob"]:
            weights = weights / weights.sum(-1, keepdims=True)
        load_balance = width * jnp.sum(chosen.sum(0) / (batch * seq) * scores.mean(0))

        def add_expert(y, xs):
            weight, w_gate, w_up, w_down = xs
            return y + expert(h, weight.astype(f), w_gate, w_up, w_down), None

        y, _ = jax.lax.scan(add_expert, jnp.zeros_like(h),
                            (weights.T[first:first + held], moe["w_gate"], moe["w_up"], moe["w_down"]))
        return y.reshape(batch, seq, d), chosen, load_balance

    @jax.checkpoint
    def block(x, layer, kept_by_system):
        layer = jax.tree.map(lambda p: p.astype(f), layer)
        h = rms_norm(x, layer["attn_norm"], eps)
        rows = [attention_of_row(b, h[b], layer, None if kept_by_system is None else kept_by_system[b])
                for b in range(batch)]
        o, kl, n_kept, differs, keep = (None if part[0] is None else jnp.stack(part) for part in zip(*rows))
        x = x + o @ layer["wo"].reshape(nh * hd, d)
        y, chosen, load_balance = experts(rms_norm(x, layer["mlp_norm"], eps), layer["moe"])
        return x + y, (chosen, kl.sum() / (batch * seq), load_balance, n_kept.sum(), differs.sum(), keep)

    head_rows = math.gcd(seq, 2048)  # positions whose f32 logits are held at once: 16,384 x 18,992 would be 1.24 GB, thrice over

    @jax.checkpoint
    def head_chunk(x, targets, scale, table):
        logits = rms_norm(x, scale.astype(f), eps) @ table.T
        log_p = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(log_p, targets[..., None], axis=-1)[..., 0].sum()

    def head(x, scale, table):
        chunks = lambda a: jnp.moveaxis(a.reshape(batch, seq // head_rows, head_rows, *a.shape[2:]), 1, 0)
        total, _ = jax.lax.scan(lambda total, xs: (total + head_chunk(*xs, scale, table), None),
                                jnp.zeros((), jnp.float32), (chunks(x), chunks(targets)))
        return total / (batch * seq)

    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(f)[inputs]
        # A scan, so that the backward pass makes one layer's forward again at a time: as five
        # calls XLA brought the layers' recomputations forward together (12.5 GB of temporaries
        # at the published widths, which the chip has not beside the run's own state).
        x, (chosen, index_loss, load_balance, n_kept, differs, keep) = jax.lax.scan(
            lambda x, xs: block(x, *xs), x, (params["blocks"], system_keep))
        ce = head(x, params["final_norm"], params["lm_head"].astype(f))
    loss = ce + c["index_loss_weight"] * index_loss.sum() + c["aux_loss_weight"] * load_balance.sum()
    aux = {"chosen": chosen, "index_loss": index_loss.sum(), "load_balance": load_balance.sum(),
           "selected_pairs": n_kept, "selection_differs": differs}
    return loss, {**aux, "keep": keep} if return_keep else aux


# Tolerances of the agreement between the system (bf16 activations and matmul
# operands, the Pallas kernels, the packed selection, grouped matmuls over the
# held groups; f32 router, norms, indexer scores, logits and parameters) and the
# reference (f32 throughout, dense masks), at seeded initial weights, on the row
# (16,384 tokens) of the run's first batch that the harness hands `check`: here
# the whole batch. Measured on the chip at the published widths
# (`tools/keye_readings.py` and the cell's own runs; PR 42, PERF.md section 6;
# twelve readings of the system, each its own seed; five of the reference itself
# with parameters, router, norms, indexer scores and logits in bf16, the nearest
# precision below the configuration's):
#   loss              system off by 9.5e-7..9.3e-5; the bf16 reference 8.5e-4..9.3e-4
#   gradient norm     system 4.0e-5..5.6e-5; the bf16 reference 4.5e-4..4.9e-4
#   L_I               system 2.2e-6..6.5e-5 of 0.36; the bf16 reference 1.66e-3..1.71e-3
#   flipped choices   system 0.515..0.532 % of the 655,360 (token, slot) choices of
#                     the five layers; the bf16 reference 0.644..0.671 %
#   selection differs system 0.913..0.916 % of the 157.3 M selected (query, key)
#                     pairs (a key swapped counts twice: one out, one in); the bf16
#                     reference 0.991..0.995 %
# Each limit lies between its two readings. Three tell the precision with room:
# the loss (3.2 times the system's largest reading, a third of the bf16
# reference's smallest), the gradient norm (2.9 times, a third) and `L_I` (5
# times, a fifth). The two shares are made mostly by bf16 *operands*, which
# system and bf16 reference have alike (the indexer's q and k and the router's
# input go to the MXU in bf16 as the issue's equations say: a score moves by
# about 2^-9 of itself, and among 16,384 scores a key in a hundred of the 2,048
# selected lies that near the threshold), so their two readings are a quarter
# and a twelfth apart; they barely move with the seed (3 % and 0.3 % over
# twelve), and the limits stand 11 % and 3.7 % above the system's largest. What
# they are for is another function: a selection by block differs on most
# pairs; one that kept 2,047 keys a query differs on 0.05 % of them, under the
# share's own size, so the count of selected pairs is compared too, to a fifth
# of a key a query (2,047 for 2,048 is 0.875 of a key a query fewer; at these
# widths system and reference counted the same 157,291,520 in every reading,
# and ties at exact zeros move the nano model's count by 0.03). A loss without
# `L_I`, or with it at another weight, is off by 0.36; a `topk` of 2,047 or `L_I`
# over all causal keys moves `L_I` itself by far more than its limit
# (`tests/test_keye_vl2.py`). Parameters kept in bf16 are also seen by name
# (`state_dtypes_other_than_stated`), as in every cell. The readings at the
# embedding's first init (N(0, 0.02), where every token of a row chose the same
# experts) were 25 to 80 times these and told no precision: PERF.md section 6.
LOSS_ABS_TOL = 3e-4
GRAD_NORM_REL_TOL = 1.6e-4
INDEX_LOSS_REL_TOL = 3.3e-4
FLIPPED_SHARE_TOL = 5.9e-3
SELECTION_DIFFERS_TOL = 9.5e-3
SELECTED_KEYS_PER_QUERY_TOL = 0.2


def check(system: System, tokens, *, loss_tol: float = None, grad_tol: float = None, index_loss_tol: float = None,
          flipped_tol: float = None, selection_tol: float = None, selected_tol: float = None) -> Dict[str, Any]:
    """Loss and global gradient norm of the system's `loss_fn` (through the
    indexer, the selection, the attention kernels it selects, the indexer's
    loss and the held-experts layer) against the reference's, on `tokens` (a jax
    array, already placed) with the run's own parameters; `L_I` alone; what the
    routers did (`dropped` must be 0) and the share of (token, slot) choices on
    which system and reference pick different experts; what the indexers
    selected (`selection_stats`) and the share of selected (query, key) pairs on
    which system and reference differ, and the two counts of selected pairs.
    Two programs, one after the other, so
    that the two gradient trees are never held at once. A limit not given is
    the configuration's own (`check_tolerances`: the rehearsal's toy, whose sums
    run over 64 terms where the published widths' run over 2,048), else this
    file's, which are the published widths'."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import keye_vl2 as model

    cfg, mesh, c = system.cfg, system.mesh, system.c
    own = c.get("check_tolerances", {})
    limit = lambda given, name, default: default if given is None and name not in own else (
        own[name] if given is None else given)
    loss_tol = limit(loss_tol, "loss_abs", LOSS_ABS_TOL)
    grad_tol = limit(grad_tol, "grad_norm_rel", GRAD_NORM_REL_TOL)
    index_loss_tol = limit(index_loss_tol, "index_loss_rel", INDEX_LOSS_REL_TOL)
    flipped_tol = limit(flipped_tol, "flipped_share", FLIPPED_SHARE_TOL)
    selection_tol = limit(selection_tol, "selection_differs", SELECTION_DIFFERS_TOL)
    selected_tol = limit(selected_tol, "selected_keys_per_query", SELECTED_KEYS_PER_QUERY_TOL)
    params = system.state.params

    def of_system(params, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: model.loss_fn(p, {"tokens": tokens}, cfg, mesh=mesh))(params)
        walked = model.layer_stats(params, tokens, cfg)
        return (loss, optax.global_norm(grads), model.routing_stats(params, tokens, cfg, walked),
                model.selection_stats(params, tokens, cfg, walked))

    def of_reference(params, tokens, experts, keep):
        (loss, aux), grads = jax.value_and_grad(
            lambda p: reference_loss(p, tokens, c, system_keep=keep), has_aux=True)(params)
        # experts (layers, tokens, k): is each of the system's choices one of the reference's?
        same = jnp.take_along_axis(aux.pop("chosen"), experts, axis=-1)
        return loss, optax.global_norm(grads), 1.0 - same.mean(), aux

    sys_loss, sys_norm, routing, selection = jax.jit(of_system)(params, tokens)
    ref_loss, ref_norm, flipped, ref = jax.jit(of_reference)(
        params, tokens, routing.pop("experts"), selection.pop("keep"))
    got = [float(x) for x in (sys_loss, sys_norm, ref_loss, ref_norm)]
    sys_loss, sys_norm, ref_loss, ref_norm = got
    want_dtype = jnp.dtype(c["param_dtype"])
    leaves = jax.tree.leaves(params) + [
        x for x in jax.tree.leaves(system.state.opt_state) if getattr(x, "ndim", 0) > 0]
    wrong_dtype = sorted({str(x.dtype) for x in leaves if x.dtype != want_dtype})
    routing, selection, ref = jax.device_get((routing, selection, ref))
    per_expert = routing["tokens_per_expert"]
    held, elsewhere = int(routing["held_pairs"].sum()), int(routing["elsewhere_pairs"].sum())
    first = c.get("first_expert_held", 0)
    held_sizes = [[int(x) for x in layer[first:first + c["num_experts"]]] for layer in per_expert]
    sys_index, ref_index = float(selection["index_loss"].sum()), float(ref["index_loss"])
    selected, causal = float(selection["selected_pairs"].sum()), float(selection["causal_pairs"].sum())
    out = {
        "loss_system": sys_loss, "loss_reference": ref_loss,
        "grad_norm_system": sys_norm, "grad_norm_reference": ref_norm,
        "loss_abs_err": abs(sys_loss - ref_loss),
        "grad_norm_rel_err": abs(sys_norm - ref_norm) / max(ref_norm, 1e-30),
        "index_loss_system": sys_index, "index_loss_reference": ref_index,
        "index_loss_rel_err": abs(sys_index - ref_index) / max(abs(ref_index), 1e-30),
        "load_balance_reference": float(ref["load_balance"]),
        "expert_choices_flipped_share": float(flipped),
        "selection_differs_share": float(ref["selection_differs"].sum()) / max(float(ref["selected_pairs"].sum()), 1.0),
        "selected_keys_per_query_err": abs(selected - float(ref["selected_pairs"].sum())) / (
            c["num_hidden_layers"] * (tokens.shape[0] * (tokens.shape[1] - 1))),
        "state_dtypes_other_than_stated": wrong_dtype,
        "selection": {
            "selected_pairs": selected, "causal_pairs": causal,
            "selected_share": selected / max(causal, 1.0),
            "selected_pairs_reference": float(ref["selected_pairs"].sum()),
            "keys_per_query_min": int(selection["keys_per_query_min"].min()),
            "keys_per_query_max": int(selection["keys_per_query_max"].max()),
            "live_tiles": int(selection["live_tiles"].sum()), "tiles": int(selection["tiles"].sum()),
            "live_tiles_share": float(selection["live_tiles"].sum()) / max(int(selection["tiles"].sum()), 1),
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
            "tokens_per_expert_min": int(per_expert.min()),
            "tokens_per_expert_max": int(per_expert.max()),
        },
    }
    out["ok"] = bool(
        all(map(math.isfinite, got)) and out["loss_abs_err"] <= loss_tol
        and out["grad_norm_rel_err"] <= grad_tol and out["index_loss_rel_err"] <= index_loss_tol
        and not wrong_dtype and out["routing"]["dropped"] == 0
        and out["expert_choices_flipped_share"] <= flipped_tol
        and out["selection_differs_share"] <= selection_tol
        and out["selected_keys_per_query_err"] <= selected_tol)
    return out
