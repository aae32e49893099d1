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
`select` makes a tile of query rows' scores at a time and keeps the selection
alone, a bit a (query, key) pair in the form `ops/flash_attention.py` reads
(`pack_keep`), with each row's log-sum-exp of its selected scores; `index_loss`
makes the scores again a (Q tile, K tile) pair at a time beside the
probabilities.

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
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.flash_attention import (KEEP_BITS, KEEP_SPAN, LANES, KernelPlan, _causal_mask, _fwd_schedule,
                                         _keep_tile, pack_keep, unpack_keep)

INT_MIN = -(2 ** 31)
SELECT_ROWS = 64  # query rows a `select` program holds the scores of: (64, 16384) f32 is 4 MiB of VMEM
SELECT_CHUNK = 2048  # keys a product of the `select` kernel makes at a time
LOSS_TILE_Q, LOSS_TILE_K = 256, 512  # `index_loss`'s pair: what its (heads, tile_q, 64) blocks leave room for
XLA_CHUNK = 256  # query rows a step of the XLA forms holds the scores of


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
def _select_kernel(q_ref, k_ref, w_ref, keep_ref, lse_ref, keys, *, topk, rows, chunk, seq, heads):
    """One tile of `rows` queries: their scores against every key up to the
    tile's last, a chunk of keys a product, kept as sortable int32 in `keys`
    (chunks, rows, chunk); the threshold by `_threshold`; the row's log-sum-exp
    over the selected scores; the selection packed (`flash_attention.pack_keep`'s form)."""
    i = pl.program_id(1)
    row = i * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    live = ((i + 1) * rows + chunk - 1) // chunk  # the chunks that hold a key of this tile's past
    col0 = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)

    def fill(c, _):
        k_t = k_ref[0, c]  # (d, chunk)
        acc = jnp.zeros((rows, chunk), jnp.float32)
        for j in range(heads):
            s = jax.lax.dot_general(q_ref[0, j], k_t, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            acc = acc + w_ref[0, j] * jnp.maximum(s, 0.0)
        keys[c] = sortable(jnp.where(c * chunk + col0 <= row, acc, -jnp.inf))
        return 0

    jax.lax.fori_loop(0, live, fill, 0)

    def over_chunks(f, init):
        return jax.lax.fori_loop(0, live, lambda c, carry: f(carry, keys[c], c * chunk + col0 <= row), init)

    def count_at_least(t):
        return over_chunks(lambda n, ks, _: n + jnp.sum((ks >= t).astype(jnp.int32), axis=1, keepdims=True),
                           jnp.zeros((rows, 1), jnp.int32))

    tau = _threshold(count_at_least, topk, jnp.zeros((rows, 1), jnp.int32))
    kept_scores = lambda ks, causal: jnp.where((ks >= tau) & causal, unsortable(ks), -jnp.inf)
    top = over_chunks(lambda m, ks, causal: jnp.maximum(m, jnp.max(kept_scores(ks, causal), axis=1, keepdims=True)),
                      jnp.full((rows, 1), -jnp.inf, jnp.float32))
    total = over_chunks(
        lambda z, ks, causal: z + jnp.sum(jnp.exp(kept_scores(ks, causal) - top), axis=1, keepdims=True),
        jnp.zeros((rows, 1), jnp.float32))
    lse_ref[0] = top + jnp.log(total)

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    for span in range(keep_ref.shape[2] // LANES):
        word = jnp.zeros((rows, LANES), jnp.int32)
        for b in range(KEEP_BITS):
            first = span * KEEP_SPAN + b * LANES
            if first >= seq:
                break
            c, at = divmod(first, chunk)
            # A chunk past `live` was never filled: every key of it lies in the tile's future.
            bit = (keys[c, :, at:at + LANES] >= tau) & (first + lane <= row)
            word = word | (bit.astype(jnp.int32) << b)
        keep_ref[0, :, span * LANES:(span + 1) * LANES] = word


def _pallas_select(q_i, k_i, w, topk: int, interpret: bool):
    batch, heads, seq, d = q_i.shape
    rows, chunk = int(np.gcd(seq, SELECT_ROWS)), int(np.gcd(seq, SELECT_CHUNK))
    spans = -(-seq // KEEP_SPAN)
    k_t = k_i.reshape(batch, seq // chunk, chunk, d).transpose(0, 1, 3, 2)  # (batch, chunks, d, chunk)
    weights = w.astype(jnp.float32).transpose(0, 2, 1)[..., None]  # (batch, heads, seq, 1)
    with jax.named_scope("select"):
        keep, lse = pl.pallas_call(
            functools.partial(_select_kernel, topk=topk, rows=rows, chunk=chunk, seq=seq, heads=heads),
            grid=(batch, seq // rows),
            in_specs=[pl.BlockSpec((1, heads, rows, d), lambda b, i: (b, 0, i, 0)),
                      pl.BlockSpec((1, seq // chunk, d, chunk), lambda b, i: (b, 0, 0, 0)),
                      pl.BlockSpec((1, heads, rows, 1), lambda b, i: (b, 0, i, 0))],
            out_specs=[pl.BlockSpec((1, rows, spans * LANES), lambda b, i: (b, i, 0)),
                       pl.BlockSpec((1, rows, 1), lambda b, i: (b, i, 0))],
            out_shape=[jax.ShapeDtypeStruct((batch, seq, spans * LANES), jnp.int32),
                       jax.ShapeDtypeStruct((batch, seq, 1), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((seq // chunk, rows, chunk), jnp.int32)],
            interpret=interpret,
            name="select",
            compiler_params=None if interpret else pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
        )(q_i, k_t, weights)
    return keep, lse[..., 0]


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
def _index_loss_kernel(steps_ref, q_ref, k_ref, lse_ref, keep_ref, qi_ref, ki_ref, w_ref, lsei_ref,
                       loss_ref, dqi_ref, dw_ref, dki_ref, p_acc, dqi_acc, dw_acc, loss_acc, *,
                       sm_scale, tile_q, tile_k, heads, index_heads):
    """Grid (batch, pairs, heads), the last two sequential. A head a step adds
    its probabilities of the pair to `p_acc`; the last head's step makes the
    pair's index scores, the loss's terms, the gradient `softmax(I) - P` and
    its way through the scores: `dqi_acc`, `dw_acc` and `loss_acc` gather over
    the Q tile's pairs, the pair's share of dkI goes out as a block of its own."""
    t, h = pl.program_id(1), pl.program_id(2)
    i, j = steps_ref[0, t], steps_ref[1, t]
    first, last = steps_ref[2, t] == 1, steps_ref[4, t] == 1

    @pl.when(first & (h == 0))
    def _():
        dqi_acc[...] = jnp.zeros_like(dqi_acc)
        dw_acc[...] = jnp.zeros_like(dw_acc)
        loss_acc[...] = jnp.zeros_like(loss_acc)

    @pl.when(h == 0)
    def _():
        p_acc[...] = jnp.zeros_like(p_acc)

    q = (q_ref[0].astype(jnp.float32) * sm_scale).astype(q_ref.dtype)
    s = jax.lax.dot_general(q, k_ref[0], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    # What is not selected may overflow here: the select below lets none of it through.
    p_acc[...] += jnp.exp(s - lse_ref[0])

    @pl.when(h == heads - 1)
    def _():
        mask = _keep_tile(keep_ref, j, tile_k) & _causal_mask(tile_q, tile_k)(i, j)
        p = jnp.where(mask, p_acc[...] * (1.0 / heads), 0.0)
        k_i, w = ki_ref[0], w_ref[0]
        lane = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
        column = lambda n: jnp.sum(jnp.where(lane == n, w, 0.0), axis=1, keepdims=True)  # (tile_q, 1)
        score = lambda n: jax.lax.dot_general(qi_ref[0, n], k_i, (((1,), (1,)), ((), ())),
                                              preferred_element_type=jnp.float32)
        weights = [column(n) for n in range(index_heads)]
        scores = sum(weights[n] * jnp.maximum(score(n), 0.0) for n in range(index_heads))
        log_q = scores - lsei_ref[0]
        positive = p > 0
        loss_acc[...] += jnp.sum(
            jnp.where(positive, p * (jnp.log(jnp.where(positive, p, 1.0)) - log_q), 0.0), axis=1, keepdims=True)
        d_scores = jnp.where(mask, jnp.exp(log_q), 0.0) - p
        dk = jnp.zeros(k_i.shape, jnp.float32)
        dw = jnp.zeros(w.shape, jnp.float32)
        for n in range(index_heads):
            s_n = score(n)
            g = jnp.where(s_n > 0, d_scores * weights[n], 0.0).astype(k_i.dtype)
            dqi_acc[n] += jax.lax.dot_general(g, k_i, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            dk = dk + jax.lax.dot_general(g, qi_ref[0, n], (((0,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32)
            dw = dw + jnp.where(lane == n, jnp.sum(d_scores * jnp.maximum(s_n, 0.0), axis=1, keepdims=True), 0.0)
        dw_acc[...] += dw
        dki_ref[0, 0] = dk

        @pl.when(last)
        def _():
            loss_ref[0] = loss_acc[...]
            dqi_ref[0] = dqi_acc[...].astype(dqi_ref.dtype)
            dw_ref[0] = dw_acc[...]


def _pallas_index_loss(q, k, lse, keep, q_i, k_i, w, lse_i, sm_scale, interpret):
    """(the loss, d loss / d q_i, d k_i, d w), the mean's 1 / (batch * seq) in all four."""
    batch, heads, seq, d = q.shape
    kv_heads, index_heads, d_i = k.shape[1], q_i.shape[1], q_i.shape[3]
    tile_q, tile_k = int(np.gcd(seq, LOSS_TILE_Q)), int(np.gcd(seq, LOSS_TILE_K))
    n_q = seq // tile_q
    plan = KernelPlan(tile_q, tile_k, 0, 0, 0, False)
    steps = _fwd_schedule(seq, plan, True)
    group = heads // kv_heads
    per_span = KEEP_SPAN // tile_k
    w_lanes = jnp.pad(w.astype(jnp.float32), ((0, 0), (0, 0), (0, LANES - index_heads)))
    row = lambda width: pl.BlockSpec((1, tile_q, width), lambda b, t, h, steps: (b, steps[0, t], 0))
    with jax.named_scope("index_loss"):
        loss, dq_i, dw, dk_parts = pl.pallas_call(
            functools.partial(_index_loss_kernel, sm_scale=sm_scale, tile_q=tile_q, tile_k=tile_k,
                              heads=heads, index_heads=index_heads),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(batch, steps.shape[1], heads),
                in_specs=[
                    pl.BlockSpec((1, tile_q, d), lambda b, t, h, steps: (b * heads + h, steps[0, t], 0)),
                    pl.BlockSpec((1, tile_k, d),
                                 lambda b, t, h, steps: (b * kv_heads + h // group, steps[1, t], 0)),
                    pl.BlockSpec((1, tile_q, 1), lambda b, t, h, steps: (b * heads + h, steps[0, t], 0)),
                    pl.BlockSpec((1, tile_q, LANES),
                                 lambda b, t, h, steps: (b, steps[0, t], steps[1, t] // per_span)),
                    pl.BlockSpec((1, index_heads, tile_q, d_i), lambda b, t, h, steps: (b, 0, steps[0, t], 0)),
                    pl.BlockSpec((1, tile_k, d_i), lambda b, t, h, steps: (b, steps[1, t], 0)),
                    row(LANES), row(1)],
                out_specs=[
                    row(1),
                    pl.BlockSpec((1, index_heads, tile_q, d_i), lambda b, t, h, steps: (b, 0, steps[0, t], 0)),
                    row(LANES),
                    pl.BlockSpec((1, 1, tile_k, d_i), lambda b, t, h, steps: (b, steps[0, t], steps[1, t], 0))],
                scratch_shapes=[pltpu.VMEM((tile_q, tile_k), jnp.float32),
                                pltpu.VMEM((index_heads, tile_q, d_i), jnp.float32),
                                pltpu.VMEM((tile_q, LANES), jnp.float32),
                                pltpu.VMEM((tile_q, 1), jnp.float32)]),
            out_shape=[jax.ShapeDtypeStruct((batch, seq, 1), jnp.float32),
                       jax.ShapeDtypeStruct(q_i.shape, q_i.dtype),
                       jax.ShapeDtypeStruct((batch, seq, LANES), jnp.float32),
                       jax.ShapeDtypeStruct((batch, n_q, seq, d_i), jnp.float32)],
            interpret=interpret,
            name="index_loss",
            compiler_params=None if interpret else pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        )(jnp.asarray(steps), q.reshape(-1, seq, d), k.reshape(-1, seq, d), lse.reshape(-1, seq, 1), keep,
          q_i, k_i, w_lanes, lse_i[..., None])
        # A Q tile wrote the K tiles of its past and no other block of its part.
        visited = (np.arange(seq)[None, :] // tile_k) * tile_k < (np.arange(n_q)[:, None] + 1) * tile_q
        dk_i = jnp.sum(jnp.where(visited[None, :, :, None], dk_parts, 0.0), axis=1)
        scale = 1.0 / (batch * seq)
        return (jnp.sum(loss) * scale, (dq_i.astype(jnp.float32) * scale).astype(q_i.dtype),
                (dk_i * scale).astype(k_i.dtype), dw[..., :index_heads] * scale)


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
