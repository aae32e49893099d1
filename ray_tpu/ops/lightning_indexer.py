"""Learned sparse attention's three operators round the attention call
(DeepSeek-V3.2-Exp's "lightning indexer", as Keye-VL-2.0's `sa_config` sizes
it): the indexer's scores, the exact top-k selection of keys they make for
every query, and the loss that teaches the indexer the attention's own
distribution.

    scores     I_ts = sum_j w_tj relu(qI_tj . kI_s)           f32, s <= t
    selection  tau_t = the k-th largest of {I_ts : s <= t};  S_t = {s <= t : I_ts >= tau_t}
               (every s <= t where there are no more than k; ties at tau_t all kept)
    loss       P_ts = mean over heads of softmax_{S_t}(q_t . k_s / sqrt(d));  no gradient
               L = mean_t sum_{s in S_t} P_ts (log P_ts - log softmax_{S_t}(I_t.)_s)

An S x S matrix of f32 scores is 1.07 GB a row of 16,384 and is never made:
`select` makes the scores of 128 queries at a time and keeps the selection
alone, a bit a (query, key) pair in the form `ops/flash_attention.py` reads
(`pack_keep`), with each row's log-sum-exp of its selected scores; `index_loss`
makes the scores again a (Q tile, K tile) pair at a time beside the
probabilities: a program of its kernel is a whole pair, every attention head
(a key/value head read once for its group) and every indexer head inside it.
Both kernels lay their tiles keys down and queries across, so that what belongs
to a query (a head's log-sum-exp, an indexer head's weight, the threshold and
the count of `select`'s search, the loss's term) is a row and no column is ever
spread over the lanes (`index_loss` since PR 43, `select` since PR 49: a count
is then whole vregs added down the keys, and the keys stream through the MXU
against a head's stationary queries). In `index_loss` the indexer's 64-wide
heads lie two to a row of 128 lanes; the pair's index scores are made once and
kept for the gradient where `_loss_plan` counts room for them, and the tiles
are what that plan derives from the shapes and the bytes a program holds.
`select`'s program holds the sortable scores of its 128 queries against every
key of their past, (16384, 128) int32 = 8 MiB at the Keye cell's row, and `kI`
comes in by copies of a chunk (`_select_bytes`, `_select_plan`).

The k-th largest is found on the bit pattern: an f32 maps to an int32 whose
order is the float's (`sortable`), and 32 rounds of compare-and-count, one a
bit from the top, build the largest threshold that at least k keys reach.
Exact, no sort: a key-value sort costs the v5e 0.8 ns an element (PERF.md
section 6, PR 38), 0.21 s a layer for 16,384 rows of 16,384, and `lax.top_k`
at k = 2,048 is a sort.

The loss's gradient with respect to the scores, `softmax_{S_t}(I) - P`, needs
no incoming cotangent but a scalar, so `index_loss`'s kernel forms it where
`P` is, takes it on through the scores to the indexer's three inputs in the
same pair, and keeps those three gradients (bf16 `qI`'s 33.5 MB a row of
16,384, 4 MB and 1 MB for the others) for the backward pass, which scales
them: `P` is made once a step, not twice. The price is the three gradients'
bytes from the forward pass to the backward.

Each function has an XLA form, the same mathematics in chunks of queries: what
runs off the TPU, and the tests' yardstick for the kernels.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.flash_attention import (KEEP_BITS, KEEP_SPAN, LANES, KernelPlan, _fwd_schedule,
                                         pack_keep, unpack_keep)

INT_MIN = -(2 ** 31)
SUBLANES = 8
# A `select` program: its queries across the lanes (a full lane row; fewer fill part of one and hold as much),
# and the keys a pass takes at a time, both cut to the row by gcd. At (1, 16, 16384, 64), top 2,048, a program
# holds 10.27 MiB by `_select_bytes` (8 MiB the sortable scores; the v5e's compiler takes 9.25, the smallest
# `vmem_limit_bytes` that compiles, under the default with none asked for, alone and inside the Keye step).
# Kernel ms a call on the v5e by chunk (tools/select_bench.py, PR 49; PERF.md section 6): 256 7.28, 512 6.52,
# 1024 9.69, 2048 10.21: the search's pass, a chunk's 64 vregs of keys against one of candidates, is 2.73 ms
# at 512 and 5.94 at 1,024, where a chunk no longer fits the vregs; what this replaced (64 queries down the
# sublanes, keys across) 10.77.
SELECT_QUERIES = 128
SELECT_CHUNK = 512
# `index_loss`'s (Q tile, K tile) pairs, for `_loss_plan` to choose among by the VMEM a program holds, and what it
# may hold: Mosaic's default 16 MiB less what XLA fuses into the call's operands inside a step (a kernel that
# needs 15.4 MiB alone, 512 x 512 with the scores made twice, compiles alone and not in the Keye step), so that
# no `vmem_limit_bytes` is asked for (`flash_attention._compiler_params`). One call at (1, 32 on 4, 16384, 128),
# 16 x 64, kernel ms on the v5e (tools/index_loss_bench.py, PR 43; PERF.md section 6): scores kept 256 x 256
# 16.81, 256 x 512 17.19, 512 x 512 17.52 (the last two under a limit of 40 MiB); made twice 256 x 256 23.37,
# 256 x 512 19.97, 256 x 1024 18.94, 512 x 512 18.33, 512 x 1024 21.37; what this replaced 33.73.
LOSS_TILES = tuple((q, k) for q in (512, 256) for k in (1024, 512, 256))
LOSS_VMEM_BYTES = 14 * 2 ** 20
XLA_CHUNK = 256  # query rows a step of the XLA forms holds the scores of


def _lane_rows_of(n: int) -> int:
    """n counted up to whole rows of 128 lanes: what a minor dimension takes in VMEM."""
    return -(-n // LANES) * LANES


def _sublane_rows_of(n: int) -> int:
    """n counted up to whole tiles of 8 sublanes."""
    return -(-n // SUBLANES) * SUBLANES


# --------------------------------------------------------------------------- the order of floats
def sortable(x):
    """f32 -> int32 with the same order: the bit pattern, its low 31 bits
    flipped where the sign is set (its own inverse on the bits). -0.0 goes in
    as 0.0: the floats compare equal, and a score of relus that are all zero
    takes its zero's sign from the weights', so a tie at zero must stay one."""
    bits = jax.lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.int32)
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def unsortable(keys):
    return jax.lax.bitcast_convert_type(keys ^ ((keys >> 31) & 0x7FFFFFFF), jnp.float32)


def _threshold(count_at_least, k: int, like):
    """The largest int32 T with `count_at_least(T) >= k`, or INT_MIN where not
    even that many keys exist, a row a lane of `like` (int32): the sign first,
    then a bit a round from the top; under one sign a set bit only raises an
    int32, so the prefix kept is the largest that k keys reach."""
    t = jnp.where(count_at_least(jnp.zeros_like(like)) >= k, 0, INT_MIN).astype(jnp.int32)

    def round_(b, t):
        candidate = t | (1 << (30 - b))
        return jnp.where(count_at_least(candidate) >= k, candidate, t)

    return jax.lax.fori_loop(0, 31, round_, t)


def _backend(seq: int, backend: Optional[str], mesh=None) -> str:
    """The kernels on the TPU (the mesh's platform where there is one, as
    `flash_attention` reads it: also when compiling ahead of time for a chip
    this process does not hold), for a row the 128 lanes divide; else the XLA forms."""
    if backend is not None:
        return backend
    platform = mesh.devices.flat[0].platform if mesh is not None else jax.default_backend()
    return "pallas" if platform == "tpu" and seq % LANES == 0 else "xla"


# --------------------------------------------------------------------------- XLA forms
def index_scores(q_i, k_i, w, start: int = 0, rows: Optional[int] = None):
    """I (batch, rows, seq) f32 for the queries `start .. start + rows` (all by
    default) against every key, the future's included. q_i (batch, heads, seq,
    d) and k_i (batch, seq, d) in the compute dtype, w (batch, seq, heads) f32."""
    rows = q_i.shape[2] - start if rows is None else rows
    with jax.named_scope("indexer"):
        q = jax.lax.dynamic_slice_in_dim(q_i, start, rows, axis=2)
        weights = jax.lax.dynamic_slice_in_dim(w, start, rows, axis=1)
        s = jnp.einsum("bjqd,bkd->bjqk", q, k_i, preferred_element_type=jnp.float32)
        return jnp.einsum("bjqk,bqj->bqk", jax.nn.relu(s), weights.astype(jnp.float32))


def _causal(start, rows: int, seq: int):
    return (start + jnp.arange(rows))[:, None] >= jnp.arange(seq)[None, :]


def _chunks(seq: int) -> int:
    return int(np.gcd(seq, XLA_CHUNK))


def _xla_select(q_i, k_i, w, topk: int):
    seq, rows = q_i.shape[2], _chunks(q_i.shape[2])

    def chunk(start):
        causal = _causal(start, rows, seq)
        scores = jnp.where(causal, index_scores(q_i, k_i, w, start, rows), -jnp.inf)
        keys = sortable(scores)
        count = lambda t: jnp.sum(keys >= t, axis=-1, keepdims=True, dtype=jnp.int32)
        kept = (keys >= _threshold(count, topk, keys[..., :1])) & causal
        lse = jax.scipy.special.logsumexp(jnp.where(kept, scores, -jnp.inf), axis=-1)
        return pack_keep(kept), lse

    keep, lse = jax.lax.map(chunk, jnp.arange(0, seq, rows))  # (chunks, batch, rows, ...)
    return (jnp.moveaxis(keep, 0, 1).reshape(q_i.shape[0], seq, -1),
            jnp.moveaxis(lse, 0, 1).reshape(q_i.shape[0], seq))


def _xla_index_loss(q, k, lse, keep, q_i, k_i, w, sm_scale):
    batch, heads, seq, _ = q.shape
    rows = _chunks(seq)
    k = jnp.repeat(k, heads // k.shape[1], axis=1)
    kept_all = unpack_keep(keep, seq)

    @jax.checkpoint
    def chunk(start):
        kept = jax.lax.dynamic_slice_in_dim(kept_all, start, rows, axis=1) & _causal(start, rows, seq)
        qs = jax.lax.dynamic_slice_in_dim(q, start, rows, axis=2)
        s = jnp.einsum("bhqd,bhkd->bhqk", qs, k, preferred_element_type=jnp.float32) * sm_scale
        p = jnp.exp(s - jax.lax.dynamic_slice_in_dim(lse, start, rows, axis=2)[..., None])
        p = jax.lax.stop_gradient(jnp.where(kept, p.mean(axis=1), 0.0))
        # The row's log-sum-exp is made again here and not read from `lse_i`: it carries
        # the softmax's half of the gradient, which the kernel writes down by hand.
        scores = index_scores(q_i, k_i, w, start, rows)
        log_q = scores - jax.scipy.special.logsumexp(
            jnp.where(kept, scores, -jnp.inf), axis=-1, keepdims=True)
        return jnp.sum(jnp.where(p > 0, p * (jnp.log(jnp.where(p > 0, p, 1.0)) - log_q), 0.0))

    return jnp.sum(jax.lax.map(chunk, jnp.arange(0, seq, rows))) / (batch * seq)


# --------------------------------------------------------------------------- the selection kernel
class SelectPlan(NamedTuple):
    """What one program of `select` is: the queries it holds across the lanes, the
    keys a product makes the scores of at a time, and the VMEM it holds."""

    queries: int
    chunk: int
    vmem_bytes: int


def _select_bytes(queries, chunk, seq, heads, d, itemsize) -> int:
    """The VMEM a program holds, counted from above as `_loss_bytes` counts: the
    sortable keys of every (key, query) of the row, a lane row of 128 whatever the
    queries are (64 queries pad to it and save nothing), the two chunks of `kI` its
    copies fill in turn (64 lanes pad to 128 as well), the blocks the pipeline
    fetches ahead (the queries' indexer heads, their weights, the packed words, the
    row statistic), and the (chunk, queries) f32 values alive at once in a chunk's
    scores: the sum, a product coming out, its relu going in, the sortable form."""
    lanes, rows = _lane_rows_of, _sublane_rows_of
    spans = -(-seq // KEEP_SPAN)
    held = seq * lanes(queries) * 4 + 2 * chunk * lanes(d) * itemsize
    blocks = 2 * (heads * rows(d) * lanes(queries) * itemsize + (rows(heads) + SUBLANES) * lanes(queries) * 4
                  + rows(queries) * spans * LANES * 4)
    return held + blocks + 4 * chunk * lanes(queries) * 4


def _select_plan(seq, heads, d, itemsize) -> SelectPlan:
    """The program `select` takes: `SELECT_QUERIES` and `SELECT_CHUNK` cut to the
    row (gcd), and what `_select_bytes` counts for them."""
    queries, chunk = int(np.gcd(seq, SELECT_QUERIES)), int(np.gcd(seq, SELECT_CHUNK))
    return SelectPlan(queries, chunk, _select_bytes(queries, chunk, seq, heads, d, itemsize))


def _select_scores(q_ref, k_hbm, w_ref, keys, k_buf, sem, row, live, *, chunk, heads):
    """The scores of the program's queries against the `live` chunks of keys that
    hold their past, a chunk a pass, into `keys` as sortable int32; the largest
    key a query has, (1, queries). `kI` stays in HBM and a chunk of it is copied
    in while the one before is multiplied: the keys stream through the MXU,
    `chunk` rows against one indexer head's (d, queries) at a time, and that
    head's weight is a row that multiplies down the sublanes."""
    b, queries, d = pl.program_id(0), row.shape[1], q_ref.shape[3]
    copy = lambda c, slot: pltpu.make_async_copy(k_hbm.at[b, c], k_buf.at[slot], sem.at[slot])
    copy(0, 0).start()
    key = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)

    def fill(c, top):
        slot = c % 2
        copy(c, slot).wait()

        @pl.when(c + 1 < live)
        def _():
            copy(c + 1, 1 - slot).start()

        k = k_buf[slot, :, :d]
        acc = jnp.zeros((chunk, queries), jnp.float32)
        for j in range(heads):
            s = jax.lax.dot_general(k, q_ref[0, 0, j], (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            acc = acc + w_ref[0, 0, j:j + 1, :] * jnp.maximum(s, 0.0)
        ks = sortable(jnp.where(c * chunk + key <= row, acc, -jnp.inf)).reshape(chunk // SUBLANES, SUBLANES, queries)
        keys[c] = ks
        return jnp.maximum(top, jnp.max(ks, axis=0))

    top = jax.lax.fori_loop(0, live, fill, jnp.full((SUBLANES, queries), INT_MIN, jnp.int32))
    return jnp.max(top, axis=0, keepdims=True)


def _select_search(keys, live, topk, queries):
    """`_threshold` over the live chunks, (1, queries) int32. A round compares every
    key with its query's candidate, a row spread down the sublanes once a round,
    and adds whole vregs down the keys into one (8, queries) count, folded once a
    round. A key of the last live chunk that lies in a query's future is -inf's
    and counts for a candidate no finite score reaches: for a row shorter than
    `topk` alone, which keeps its whole past whatever the threshold."""
    def count_at_least(t):
        spread = jnp.broadcast_to(t, (SUBLANES, queries))
        count = jax.lax.fori_loop(0, live, lambda c, n: n + jnp.sum((keys[c] >= spread).astype(jnp.int32), axis=0),
                                  jnp.zeros((SUBLANES, queries), jnp.int32))
        return jnp.sum(count, axis=0, keepdims=True)

    return _threshold(count_at_least, topk, jnp.zeros((1, queries), jnp.int32))


def _select_lse(keys, live, tau, top):
    """The log-sum-exp of a query's selected scores, (1, queries) f32. The largest
    score is always selected, so the row's largest key is the maximum; a key of
    the future is -inf's and adds exp(-inf) = 0 where the threshold lets it through."""
    queries = tau.shape[1]
    top = unsortable(top)
    tau, most = jnp.broadcast_to(tau, (SUBLANES, queries)), jnp.broadcast_to(top, (SUBLANES, queries))

    def add(c, total):
        ks = keys[c]
        return total + jnp.sum(jnp.where(ks >= tau, jnp.exp(unsortable(ks) - most), 0.0), axis=0)

    total = jax.lax.fori_loop(0, live, add, jnp.zeros((SUBLANES, queries), jnp.float32))
    return top + jnp.log(jnp.sum(total, axis=0, keepdims=True))


def _select_pack(keep_ref, keys, tau, row, *, chunk, seq):
    """The selection in `flash_attention.pack_keep`'s form, queries down: a span's
    32 blocks of 128 keys go into a (128 keys, queries) word a bit each, and the
    span's words are turned over once. The blocks under the program's first query
    are every query's past; those from its last on are never read (a chunk past
    `live` was never filled); the causal test is made for the blocks between."""
    queries = row.shape[1]
    first_row = pl.program_id(1) * queries
    key = jax.lax.broadcasted_iota(jnp.int32, (LANES, 1), 0)
    blocks = LANES // SUBLANES
    for span in range(keep_ref.shape[2] // LANES):
        def add(b, word, crossed, span=span):
            first = span * KEEP_SPAN + b * LANES
            c, at = first // chunk, pl.multiple_of((first % chunk) // SUBLANES, blocks)
            kept = keys[c, pl.ds(at, blocks)].reshape(LANES, queries) >= tau
            if crossed:
                kept = kept & (first + key <= row)
            return word | (kept.astype(jnp.int32) << b)

        bits = min(KEEP_BITS, (seq - span * KEEP_SPAN) // LANES)
        past = jnp.clip((first_row - span * KEEP_SPAN) // LANES, 0, bits)
        live = jnp.clip((first_row + queries - span * KEEP_SPAN + LANES - 1) // LANES, 0, bits)
        word = jax.lax.fori_loop(0, past, functools.partial(add, crossed=False), jnp.zeros((LANES, queries), jnp.int32))
        word = jax.lax.fori_loop(past, live, functools.partial(add, crossed=True), word)
        keep_ref[0, :, span * LANES:(span + 1) * LANES] = word.T


def _select_kernel(q_ref, k_hbm, w_ref, keep_ref, lse_ref, keys, k_buf, sem, *, topk, queries, chunk, seq, heads):
    """One program: `queries` queries across the lanes, the keys down. Their scores
    against every key up to the program's last, kept as sortable int32 in `keys`
    (chunks, chunk / 8, 8, queries); the threshold by `_threshold`; the row's
    log-sum-exp over the selected scores; the selection packed. What belongs to a
    query (an indexer head's weight, the threshold, a count, the row statistic) is
    a (1, queries) row; the causal test is key index (down) <= query index (across)."""
    i = pl.program_id(1)
    row = i * queries + jax.lax.broadcasted_iota(jnp.int32, (1, queries), 1)
    live = ((i + 1) * queries + chunk - 1) // chunk  # the chunks that hold a key of this program's past
    top = _select_scores(q_ref, k_hbm, w_ref, keys, k_buf, sem, row, live, chunk=chunk, heads=heads)
    tau = _select_search(keys, live, topk, queries)
    lse_ref[0, 0] = _select_lse(keys, live, tau, top)
    _select_pack(keep_ref, keys, tau, row, chunk=chunk, seq=seq)


def _pallas_select(q_i, k_i, w, topk: int, interpret: bool):
    batch, heads, seq, d = q_i.shape
    assert seq % LANES == 0, f"the selection packs blocks of {LANES} keys: a row of {seq} has a part of one"
    plan = _select_plan(seq, heads, d, q_i.dtype.itemsize)
    queries, chunk = plan.queries, plan.chunk
    n, spans = seq // queries, -(-seq // KEEP_SPAN)
    # A program's queries across the lanes: (batch, programs, heads, d, queries) and the weights beside them.
    q_t = q_i.reshape(batch, heads, n, queries, d).transpose(0, 2, 1, 4, 3)
    weights = w.astype(jnp.float32).reshape(batch, n, queries, heads).transpose(0, 1, 3, 2)
    # A copy moves whole lane rows: the keys' d numbers at the head of a row of 128, as VMEM would hold them anyway.
    k_rows = jnp.pad(k_i, ((0, 0), (0, 0), (0, -d % LANES))).reshape(batch, seq // chunk, chunk, -1)
    with jax.named_scope("select"):
        keep, lse = pl.pallas_call(
            functools.partial(_select_kernel, topk=topk, queries=queries, chunk=chunk, seq=seq, heads=heads),
            grid=(batch, n),
            in_specs=[pl.BlockSpec((1, 1, heads, d, queries), lambda b, i: (b, i, 0, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((1, 1, heads, queries), lambda b, i: (b, i, 0, 0))],
            out_specs=[pl.BlockSpec((1, queries, spans * LANES), lambda b, i: (b, i, 0)),
                       pl.BlockSpec((1, 1, 1, queries), lambda b, i: (b, i, 0, 0))],
            out_shape=[jax.ShapeDtypeStruct((batch, seq, spans * LANES), jnp.int32),
                       jax.ShapeDtypeStruct((batch, n, 1, queries), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((seq // chunk, chunk // SUBLANES, SUBLANES, queries), jnp.int32),
                            pltpu.VMEM((2, *k_rows.shape[2:]), k_i.dtype), pltpu.SemaphoreType.DMA((2,))],
            interpret=interpret,
            name="select",
            compiler_params=None if interpret else pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
        )(q_t, k_rows, weights)
    return keep, lse.reshape(batch, seq)


def select(q_i, k_i, w, topk: int, backend: Optional[str] = None, interpret: bool = False, mesh=None):
    """(keep (batch, seq, spans * 128) int32, lse (batch, seq) f32): for every
    query the keys of its past whose score is among its `topk` largest (the
    module's docstring), packed as `flash_attention(keep=)` takes them, and the
    log-sum-exp of the selected scores. No gradient: the selection is a choice."""
    q_i, k_i, w = jax.lax.stop_gradient((q_i, k_i, w))
    if _backend(q_i.shape[2], backend, mesh) == "xla":
        with jax.named_scope("select"):
            return _xla_select(q_i, k_i, w, topk)
    return _pallas_select(q_i, k_i, w, topk, interpret)


# --------------------------------------------------------------------------- the loss kernel
def _kept_pairs(keep_ref, i, j, tile_q, tile_k):
    """The pair's selected and causal (key, query) bool, keys down and queries
    across: `flash_attention._keep_tile` and `_causal_mask` turned over. The
    packed block is (tile_q, 128) words, bit b of word [q, lane] the key
    `b * 128 + lane` of its span: turned, a bit is a (128, tile_q) slab of keys."""
    bits = tile_k // LANES
    words = keep_ref[0].T  # (128, tile_q)
    first = (j % (KEEP_SPAN // tile_k)) * bits
    kept = jnp.concatenate([(words >> (first + b)) & 1 for b in range(bits)], axis=0) != 0
    diff = (jax.lax.broadcasted_iota(jnp.int32, (tile_k, tile_q), 1)
            - jax.lax.broadcasted_iota(jnp.int32, (tile_k, tile_q), 0))
    return kept & (diff >= j * tile_k - i * tile_q)


def _pair_probabilities(qs, k_ref, lse_ref, *, heads, kv_heads):
    """Sum over the heads of exp(q_h . k - lse_h), (tile_k, tile_q) f32: a key/value
    head is read once for its group of query heads, a head's log-sum-exp is a row
    that goes down the sublanes. What is not selected may overflow here: the
    caller's select lets none of it through."""
    group = heads // kv_heads
    p = None
    for g in range(kv_heads):
        k = k_ref[0, g]
        for h in range(g * group, (g + 1) * group):
            s = jax.lax.dot_general(k, qs[h], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            e = jnp.exp(s - lse_ref[0, h:h + 1, :])
            p = e if p is None else p + e
    return p


def _lane_rows(index_heads: int, d_i: int):
    """(the indexer's heads that lie side by side in a row of 128 lanes, the heads
    counted up to whole rows): 2 and 16 at 16 heads of 64; 4 and 4 at 3 heads of 32."""
    pack = max(LANES // d_i, 1)
    return pack, -(-index_heads // pack) * pack


def _pair_gradients(p, mask, qi_ref, ki_ref, w_ref, lsei_ref, dqi_acc, dw_acc, loss_acc, dki_ref, *,
                    index_heads, d_i, keep_scores):
    """The pair's index scores, its terms of the loss, `softmax(I) - P` and that
    gradient's way through the scores, keys down and queries across. The indexer's
    heads lie `pack` to a row of 128 lanes (as `_pallas_index_loss` lays them): head
    n's product is the lane row's against the keys at that head's lanes, zeros at
    the others', as deep and as dear on the MXU as its own 64 lanes alone. With
    `keep_scores` a head's f32 scores stay from the sum to the gradient; else their
    product is made again there: three products a head or four, all 64 deep."""
    tile_k, tile_q = p.shape
    pack, _ = _lane_rows(index_heads, d_i)
    width = pack * d_i
    k_all = ki_ref[0]  # (tile_k, width): the key's d_i numbers, `pack` times over
    slot = jax.lax.broadcasted_iota(jnp.int32, k_all.shape, 1) // d_i
    k_at = [k_all if pack == 1 else jnp.where(slot == r, k_all, jnp.zeros_like(k_all)) for r in range(pack)]
    lanes = lambda n: slice((n // pack) * width, (n // pack + 1) * width)  # head n's row of lanes
    rows = lambda n: qi_ref[0, :, lanes(n)]  # (tile_q, width)
    score = lambda n: jax.lax.dot_general(k_at[n % pack], rows(n), (((1,), (1,)), ((), ())),
                                          preferred_element_type=jnp.float32)  # (tile_k, tile_q)
    weight = lambda n: w_ref[0, n:n + 1, :]  # (1, tile_q)

    made = [score(n) for n in range(index_heads)] if keep_scores else None
    again = (lambda n: made[n]) if keep_scores else score
    scores = sum(weight(n) * jnp.maximum(again(n), 0.0) for n in range(index_heads))
    log_q = scores - lsei_ref[0]
    positive = p > 0
    loss_acc[...] += jnp.sum(
        jnp.where(positive, p * (jnp.log(jnp.where(positive, p, 1.0)) - log_q), 0.0), axis=0, keepdims=True)
    d_scores = jnp.where(mask, jnp.exp(log_q), 0.0) - p

    dk = [jnp.zeros((tile_k, width), jnp.float32) for _ in range(pack)]
    for n in range(index_heads):
        s_n = again(n)
        through = jnp.where(s_n > 0, d_scores, 0.0)  # d loss / d relu's input, the weight aside
        dw_acc[n:n + 1, :] += jnp.sum(through * s_n, axis=0, keepdims=True)
        g = (through * weight(n)).astype(k_all.dtype)
        dqi_acc[:, lanes(n)] += jax.lax.dot_general(g, k_at[n % pack], (((0,), (0,)), ((), ())),
                                                 preferred_element_type=jnp.float32)
        dk[n % pack] = dk[n % pack] + jax.lax.dot_general(g, rows(n), (((1,), (0,)), ((), ())),
                                                          preferred_element_type=jnp.float32)
    # Slot r's product holds head r's dkI at its own lanes and another head's pairing at the rest.
    dki_ref[0, 0] = sum(dk[r][:, r * d_i:(r + 1) * d_i] for r in range(pack))


class LossPlan(NamedTuple):
    """What one program of `index_loss` is: its (Q tile, K tile) pair, whether it
    keeps the pair's index scores between their two uses, and the VMEM it holds."""

    tile_q: int
    tile_k: int
    keep_scores: bool
    vmem_bytes: int


def _loss_bytes(tile_q, tile_k, keep_scores, heads, kv_heads, d, index_heads, d_i, itemsize) -> int:
    """The VMEM a program holds, counted from above: every block at the lanes and
    sublanes it pads to, twice where the pipeline fetches it ahead of a pair (what
    changes with the Q tile alone is held once), the scratch, and the (tile_k,
    tile_q) f32 values alive at once: the probabilities, the mask, the scores'
    sum, a product coming out and one going in, and with `keep_scores` one an
    indexer head. The smallest `vmem_limit_bytes` that compiles for the v5e at
    (32 on 4, 128), 16 x 64 in bf16 (ahead-of-time compiles, PR 43): 256 x 256
    kept 10.75 MiB for the 12.8 counted here, 256 x 512 kept 16.5 for 18.6, not
    kept 256 x 512 9.5 for 10.6, 256 x 1024 13.6 for 14.4, 512 x 1024 20.3 for 25.2."""
    lanes, rows = _lane_rows_of, _sublane_rows_of
    pack, padded = _lane_rows(index_heads, d_i)
    lane_rows = lanes(padded * d_i)
    of_q_tile = (2 * heads * tile_q * lanes(d) * itemsize  # q and its scaled copy
                 + (rows(heads) + 4 * rows(index_heads) + 4 * 8) * tile_q * 4  # lse; w, dw twice, its sum; lse_i, the loss
                 + tile_q * lane_rows * (3 * itemsize + 4))  # q_i, dq_i twice, its f32 sum
    of_pair = 2 * (kv_heads * tile_k * lanes(d) * itemsize + tile_q * LANES * 4
                   + tile_k * lanes(pack * d_i) * itemsize + tile_k * lanes(d_i) * 4)  # k, keep, k_i, dk_i's share
    alive = 4 + (index_heads if keep_scores else 0)
    return of_q_tile + of_pair + alive * tile_k * tile_q * 4


def _loss_plan(heads, kv_heads, seq, d, index_heads, d_i, itemsize) -> LossPlan:
    """The pair a program of `index_loss` takes, from the shapes and `_loss_bytes`:
    of `LOSS_TILES` cut to the row (gcd) the largest pair that holds `LOSS_VMEM_BYTES`
    or less, one that keeps the scores before one that makes them twice, and of two
    as large the one with more query rows (each Q tile writes a dkI share of its
    own). Where none fits, the smallest, scores made twice."""
    plans = []
    for tile_q, tile_k in LOSS_TILES:
        tile_q, tile_k = int(np.gcd(seq, tile_q)), int(np.gcd(seq, tile_k))
        for keep_scores in (True, False):
            size = _loss_bytes(tile_q, tile_k, keep_scores, heads, kv_heads, d, index_heads, d_i, itemsize)
            plans.append(LossPlan(tile_q, tile_k, keep_scores, size))
    fitting = [p for p in plans if p.vmem_bytes <= LOSS_VMEM_BYTES]
    if not fitting:
        return min(plans, key=lambda p: p.vmem_bytes)
    return max(fitting, key=lambda p: (p.keep_scores, p.tile_q * p.tile_k, p.tile_q))


def _index_loss_kernel(steps_ref, q_ref, k_ref, lse_ref, keep_ref, qi_ref, ki_ref, w_ref, lsei_ref,
                       loss_ref, dqi_ref, dw_ref, dki_ref, qs, dqi_acc, dw_acc, loss_acc, *,
                       sm_scale, tile_q, tile_k, heads, kv_heads, index_heads, d_i, keep_scores):
    """Grid (batch, pairs), the pairs in turn: a program is a whole (Q tile, K
    tile) pair, every head of it. Tiles inside are (keys, queries): what belongs
    to a query (a head's log-sum-exp, an indexer head's weight, the loss's term)
    is a row, read down the sublanes and summed down them. The Q tile's first
    pair scales its queries into `qs`; `dqi_acc`, `dw_acc` and `loss_acc` gather
    over the Q tile's pairs and leave in its last; the pair's share of dkI goes
    out as a block of its own."""
    t = pl.program_id(1)
    i, j = steps_ref[0, t], steps_ref[1, t]

    @pl.when(steps_ref[2, t] == 1)
    def _():
        def scale(h, _):
            qs[h] = (q_ref[0, h].astype(jnp.float32) * sm_scale).astype(qs.dtype)
            return 0

        jax.lax.fori_loop(0, heads, scale, 0)
        dqi_acc[...] = jnp.zeros_like(dqi_acc)
        dw_acc[...] = jnp.zeros_like(dw_acc)
        loss_acc[...] = jnp.zeros_like(loss_acc)

    mask = _kept_pairs(keep_ref, i, j, tile_q, tile_k)
    p = _pair_probabilities(qs, k_ref, lse_ref, heads=heads, kv_heads=kv_heads)
    p = jnp.where(mask, p * (1.0 / heads), 0.0)
    _pair_gradients(p, mask, qi_ref, ki_ref, w_ref, lsei_ref, dqi_acc, dw_acc, loss_acc, dki_ref,
                    index_heads=index_heads, d_i=d_i, keep_scores=keep_scores)

    @pl.when(steps_ref[4, t] == 1)
    def _():
        loss_ref[0] = loss_acc[...]
        dqi_ref[0] = dqi_acc[...].astype(dqi_ref.dtype)
        dw_ref[0] = dw_acc[...]


def _pallas_index_loss(q, k, lse, keep, q_i, k_i, w, lse_i, sm_scale, interpret):
    """(the loss, d loss / d q_i, d k_i, d w), the mean's 1 / (batch * seq) in all four."""
    batch, heads, seq, d = q.shape
    kv_heads, index_heads, d_i = k.shape[1], q_i.shape[1], q_i.shape[3]
    plan = _loss_plan(heads, kv_heads, seq, d, index_heads, d_i, q.dtype.itemsize)
    tile_q, tile_k = plan.tile_q, plan.tile_k
    n_q = seq // tile_q
    steps = _fwd_schedule(seq, KernelPlan(tile_q, tile_k, 0, 0, 0, False), True)
    per_span = KEEP_SPAN // tile_k
    # The indexer's heads side by side along the lanes, `pack` to a row of 128, zero heads to fill the last.
    pack, padded = _lane_rows(index_heads, d_i)
    q_lanes = jnp.pad(q_i, ((0, 0), (0, padded - index_heads), (0, 0), (0, 0)))
    q_lanes = q_lanes.transpose(0, 2, 1, 3).reshape(batch, seq, padded * d_i)
    k_lanes = jnp.tile(k_i, (1, 1, pack))
    once = pl.Buffered(1)  # a block that changes with the Q tile alone: nothing to fetch ahead of a pair
    of_q = lambda *block, **kw: pl.BlockSpec((1, *block), lambda b, t, steps: (b, 0, steps[0, t]), **kw)
    q_rows = lambda width, **kw: pl.BlockSpec((1, tile_q, width), lambda b, t, steps: (b, steps[0, t], 0), **kw)
    with jax.named_scope("index_loss"):
        loss, dq_lanes, dw, dk_parts = pl.pallas_call(
            functools.partial(_index_loss_kernel, sm_scale=sm_scale, tile_q=tile_q, tile_k=tile_k, heads=heads,
                              kv_heads=kv_heads, index_heads=index_heads, d_i=d_i, keep_scores=plan.keep_scores),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(batch, steps.shape[1]),
                in_specs=[
                    pl.BlockSpec((1, heads, tile_q, d), lambda b, t, steps: (b, 0, steps[0, t], 0),
                                 pipeline_mode=once),
                    pl.BlockSpec((1, kv_heads, tile_k, d), lambda b, t, steps: (b, 0, steps[1, t], 0)),
                    of_q(heads, tile_q, pipeline_mode=once),
                    pl.BlockSpec((1, tile_q, LANES), lambda b, t, steps: (b, steps[0, t], steps[1, t] // per_span)),
                    q_rows(padded * d_i, pipeline_mode=once),
                    pl.BlockSpec((1, tile_k, pack * d_i), lambda b, t, steps: (b, steps[1, t], 0)),
                    of_q(index_heads, tile_q, pipeline_mode=once),
                    of_q(1, tile_q, pipeline_mode=once)],
                out_specs=[
                    of_q(1, tile_q), q_rows(padded * d_i), of_q(index_heads, tile_q),
                    pl.BlockSpec((1, 1, tile_k, d_i), lambda b, t, steps: (b, steps[0, t], steps[1, t], 0))],
                scratch_shapes=[pltpu.VMEM((heads, tile_q, d), q.dtype),
                                pltpu.VMEM((tile_q, padded * d_i), jnp.float32),
                                pltpu.VMEM((index_heads, tile_q), jnp.float32),
                                pltpu.VMEM((1, tile_q), jnp.float32)]),
            out_shape=[jax.ShapeDtypeStruct((batch, 1, seq), jnp.float32),
                       jax.ShapeDtypeStruct(q_lanes.shape, q_i.dtype),
                       jax.ShapeDtypeStruct((batch, index_heads, seq), jnp.float32),
                       jax.ShapeDtypeStruct((batch, n_q, seq, d_i), jnp.float32)],
            interpret=interpret,
            name="index_loss",
            compiler_params=None if interpret else pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
        )(jnp.asarray(steps), q, k, lse, keep, q_lanes, k_lanes, w.astype(jnp.float32).transpose(0, 2, 1),
          lse_i[:, None, :])
        # A Q tile wrote the K tiles of its past and no other block of its part.
        visited = (np.arange(seq)[None, :] // tile_k) * tile_k < (np.arange(n_q)[:, None] + 1) * tile_q
        dk_i = jnp.sum(jnp.where(visited[None, :, :, None], dk_parts, 0.0), axis=1)
        scale = 1.0 / (batch * seq)
        dq_i = dq_lanes.reshape(batch, seq, padded, d_i)[:, :, :index_heads].transpose(0, 2, 1, 3)
        return (jnp.sum(loss) * scale, (dq_i.astype(jnp.float32) * scale).astype(q_i.dtype),
                (dk_i * scale).astype(k_i.dtype), dw.transpose(0, 2, 1) * scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _kernel_index_loss(q, k, lse, keep, q_i, k_i, w, lse_i, sm_scale, interpret):
    return _pallas_index_loss(q, k, lse, keep, q_i, k_i, w, lse_i, sm_scale, interpret)[0]


def _kernel_index_loss_fwd(q, k, lse, keep, q_i, k_i, w, lse_i, sm_scale, interpret):
    loss, *grads = _pallas_index_loss(q, k, lse, keep, q_i, k_i, w, lse_i, sm_scale, interpret)
    return loss, tuple(grads)


def _kernel_index_loss_bwd(sm_scale, interpret, grads, g):
    dq_i, dk_i, dw = grads
    scaled = lambda x: (x.astype(jnp.float32) * g).astype(x.dtype)
    return None, None, None, None, scaled(dq_i), scaled(dk_i), scaled(dw), None


_kernel_index_loss.defvjp(_kernel_index_loss_fwd, _kernel_index_loss_bwd)


def index_loss(q, k, lse, keep, q_i, k_i, w, lse_i, sm_scale: Optional[float] = None,
               backend: Optional[str] = None, interpret: bool = False, mesh=None):
    """The mean over queries of KL(P_t || softmax_{S_t}(I_t)) (the module's
    docstring): q (batch, heads, seq, d), k (batch, kv heads, seq, d) and lse
    (batch, heads, seq), the attention's own row statistics under `keep`, are
    read without gradient; the gradient goes to q_i, k_i and w alone. `lse_i`
    is `select`'s second result."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    q, k, lse, lse_i = jax.lax.stop_gradient((q, k, lse, lse_i))
    if _backend(q.shape[2], backend, mesh) == "xla":
        with jax.named_scope("index_loss"):
            return _xla_index_loss(q, k, lse, keep, q_i, k_i, w, sm_scale)
    return _kernel_index_loss(q, k, lse, keep, q_i, k_i, w, lse_i, sm_scale, interpret)


# --------------------------------------------------------------------------- counters
def selection_counts(keep, tile: int = 512):
    """What a selection holds, by row of the batch summed: `selected_pairs`,
    `causal_pairs`, the fewest and the most keys a query selected, and of the
    `tile` x `tile` pairs of tiles on or under the diagonal (`tiles`) those with
    a selected key (`live_tiles`): the pairs a walk by tile could not skip."""
    batch, seq, _ = keep.shape
    tile = int(np.gcd(seq, tile))
    n = seq // tile

    def of_q_tile(i):
        kept = unpack_keep(jax.lax.dynamic_slice_in_dim(keep, i * tile, tile, axis=1), seq)
        kept = kept & _causal(i * tile, tile, seq)
        per_query = jnp.sum(kept, axis=-1, dtype=jnp.int32)
        live = jnp.any(kept.reshape(batch, tile, n, tile), axis=(1, 3))
        return per_query, jnp.sum(live, dtype=jnp.int32)

    per_query, live = jax.lax.map(of_q_tile, jnp.arange(n))
    return {
        "selected_pairs": jnp.sum(per_query.astype(jnp.float32)),
        "causal_pairs": jnp.asarray(batch * seq * (seq + 1) / 2, jnp.float32),
        "keys_per_query_min": jnp.min(per_query),
        "keys_per_query_max": jnp.max(per_query),
        "live_tiles": jnp.sum(live),
        "tiles": jnp.asarray(batch * n * (n + 1) // 2, jnp.int32),
    }
