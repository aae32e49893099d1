"""`ops/lightning_indexer.py`: the selection (`select`, the threshold on the bit pattern) and the indexer's loss
with the gradients it keeps (`index_loss`, its plan), XLA form and Pallas kernel (interpret mode on CPU) against
dense forms written here (PR 42, 43); the selection's kernel against its XLA form word for word (PR 49)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from attention_yardsticks import TOLERANCE, _dense_masked, _indexer_inputs, _scores, _top_k_set


@pytest.fixture(scope="module")
def indexed():
    return _indexer_inputs(2, 4, 512, 32)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("topk", [1, 96, 130, 511, 512, 2048])
def test_the_selection_is_lax_top_ks_set_ties_and_short_rows_included(indexed, backend, topk):
    from ray_tpu.ops import lightning_indexer  # noqa: F401  (the module, not the package's function)
    from ray_tpu.ops.flash_attention import unpack_keep
    from ray_tpu.ops.lightning_indexer import select

    q_i, k_i, w = indexed
    keep, lse = select(q_i, k_i, w, topk, backend=backend, interpret=True)
    got = np.asarray(unpack_keep(keep, 512))
    scores = _scores(q_i, k_i, w)
    want = np.asarray(_top_k_set(scores, topk))
    assert (got == want).all()
    per_query = got.sum(-1)
    assert (per_query[:, :topk] == np.arange(1, min(topk, 512) + 1)).all()  # rows shorter than k keep their past
    assert (per_query >= np.minimum(np.arange(1, 513), topk)).all()
    if topk in (96, 130):
        assert (per_query[:, topk:] > topk).any()  # the planted ties at the threshold all stay
    want_lse = jax.scipy.special.logsumexp(jnp.where(want, scores, -jnp.inf), axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse), atol=2e-5)


# the row, top k, and what stands in the module for the case: (`SELECT_QUERIES`, `SELECT_CHUNK`), None what it has
SELECT_CASES = {
    "a_row_of_384_three_programs": (384, 96, None, None),  # chunks of gcd(384, 512) = 128: a program has 1, 2, 3 live
    "ties_at_zero_past_topk": (512, 96, None, None),
    "topk_at_least_the_row": (512, 512, None, None),
    "topk_past_the_row": (384, 2048, None, None),
    "128_queries_chunks_of_128": (512, 130, 128, 128),  # four chunks in the last program: both slots of the copy
    "64_queries": (512, 130, 64, None),
    "64_queries_chunks_of_256": (512, 96, 64, 256),
    "bf16": (512, 96, None, None),
}


@pytest.mark.parametrize("case", SELECT_CASES)
def test_the_selection_kernel_keeps_the_xla_forms_keys_bit_for_bit(case, monkeypatch):
    """Queries across the lanes and keys down (PR 49) against `_xla_select`, the words of `keep` themselves."""
    import importlib

    from ray_tpu.ops.flash_attention import unpack_keep

    li = importlib.import_module("ray_tpu.ops.lightning_indexer")
    seq, topk, queries, chunk = SELECT_CASES[case]
    if queries is not None:
        monkeypatch.setattr(li, "SELECT_QUERIES", queries)
    if chunk is not None:
        monkeypatch.setattr(li, "SELECT_CHUNK", chunk)
    q_i, k_i, w = _indexer_inputs(1, 4, seq, 32)
    if case == "ties_at_zero_past_topk":
        # Query 300's weights are zeros of both signs: its 301 scores tie at zero, three times `topk`.
        w = w.at[:, 300].set(jnp.asarray([0.0, -0.0, -0.0, 0.0])).at[:, 301].set(-0.0)
    if case == "bf16":
        q_i, k_i = q_i.astype(jnp.bfloat16), k_i.astype(jnp.bfloat16)
    plan = li._select_plan(seq, 4, 32, q_i.dtype.itemsize)
    assert plan.queries == (queries or 128) and seq % plan.chunk == 0 and plan.chunk % 128 == 0
    want_keep, want_lse = li.select(q_i, k_i, w, topk, backend="xla")
    keep, lse = li.select(q_i, k_i, w, topk, backend="pallas", interpret=True)
    assert keep.shape == want_keep.shape and keep.dtype == jnp.int32 and bool((keep == want_keep).all())
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse), atol=2e-5)
    per_query = np.asarray(unpack_keep(keep, seq)).sum(-1)
    assert (per_query >= np.minimum(np.arange(1, seq + 1), topk)).all()
    if case == "ties_at_zero_past_topk":
        assert (per_query[0, 300:302] == (301, 302)).all()
    if topk >= seq:
        assert (per_query == np.arange(1, seq + 1)).all()


def test_the_selection_plan_counts_what_a_program_holds():
    """At the Keye cell's shapes a program is 128 queries across the lanes; the count is from above (the v5e's
    compiler takes the program inside its default 16 MiB: `tests/test_aot_v5e.py` compiles it, the Keye step
    too); 64 queries hold the lane rows 128 do, so a row's gcd chooses them and the bytes do not."""
    from ray_tpu.ops.lightning_indexer import LOSS_VMEM_BYTES, SELECT_CHUNK, _select_bytes, _select_plan

    plan = _select_plan(16384, 16, 64, 2)
    assert plan[:2] == (128, SELECT_CHUNK) and plan.vmem_bytes == _select_bytes(128, SELECT_CHUNK, 16384, 16, 64, 2)
    assert 8 * 2 ** 20 < plan.vmem_bytes <= LOSS_VMEM_BYTES < 16 * 2 ** 20  # what a kernel may hold inside a step
    assert _select_bytes(64, SELECT_CHUNK, 16384, 16, 64, 2) > 8 * 2 ** 20  # half the lanes, every lane row
    assert _select_bytes(128, 2048, 16384, 16, 64, 2) > LOSS_VMEM_BYTES
    for seq in (384, 512, 640, 1536, 4096):
        plan = _select_plan(seq, 4, 32, 4)
        assert seq % plan.queries == seq % plan.chunk == 0 and plan.chunk % 128 == 0


@pytest.mark.parametrize("values", ["mixed", "negative", "zeros"])
def test_the_threshold_is_the_kth_largest_on_the_bit_pattern(values):
    from ray_tpu.ops.lightning_indexer import INT_MIN, _threshold, sortable, unsortable

    x = jax.random.normal(jax.random.PRNGKey(9), (7, 300)) * 1e3
    x = {"mixed": x.at[:, ::7].set(0.0).at[:, 1::11].set(-0.0), "negative": -jnp.abs(x) - 1e-30,
         "zeros": jnp.zeros_like(x)}[values]
    keys = sortable(x)
    assert bool((unsortable(keys) == x).all())
    assert bool((unsortable(jnp.sort(keys, axis=-1)) == jnp.sort(x, axis=-1)).all())  # the floats' order
    assert bool((sortable(jnp.asarray([-0.0, 0.0])) == 0).all())  # one zero
    for k in (1, 2, 150, 300):
        count = lambda t: jnp.sum(keys >= t, axis=-1, keepdims=True, dtype=jnp.int32)
        tau = unsortable(_threshold(count, k, keys[:, :1]))
        np.testing.assert_array_equal(np.asarray(tau[:, 0]), np.asarray(jnp.sort(x, axis=-1)[:, -k]))
    none = _threshold(lambda t: jnp.sum(keys >= t, axis=-1, keepdims=True, dtype=jnp.int32), 301, keys[:, :1])
    assert bool((none == INT_MIN).all())  # fewer keys than k: everything is at or above it


# batch, heads on key/value heads, row, head_dim, the indexer's heads x width, top k, operands, (Q tile, K tile)
# where the case asks for tiles of its own: else what `_loss_plan` gives the shape
INDEX_LOSS_CASES = {
    "one_pair": (2, 4, 2, 512, 64, 4, 32, 96, jnp.float32, None),
    # Q tiles of 256 on K tiles of 512: the third Q tile has two K tiles, the last crossed by the
    # diagonal at its upper half, the fourth's at its lower; a group is 4 heads on a key/value head.
    "several_k_tiles_8_on_2": (1, 8, 2, 1536, 64, 4, 32, 200, jnp.float32, (256, 512)),
    "the_plans_own_tiles_of_1536": (1, 8, 2, 1536, 64, 4, 32, 200, jnp.float32, None),  # 512 x 256: two K tiles a diagonal
    "three_indexer_heads": (1, 4, 2, 512, 64, 3, 32, 96, jnp.float32, (256, 256)),  # they fill no row of 128 lanes
    "a_row_no_tile_divides": (1, 4, 2, 640, 64, 4, 32, 96, jnp.float32, None),  # 5 x 5 tiles of gcd(640, .) = 128
    "two_heads_a_lane_row": (1, 4, 1, 512, 64, 6, 64, 96, jnp.float32, (128, 256)),  # the Keye cell's 64-wide heads
    "scores_made_twice": (1, 4, 2, 512, 64, 4, 32, 96, jnp.float32, (256, 128, False)),
    "bf16": (1, 8, 2, 1024, 64, 4, 32, 128, jnp.bfloat16, (256, 256)),
}


@functools.lru_cache(maxsize=None)
def _index_loss_yardstick(case):
    """A case's operands and what the dense form gives for them, made once for both backends: (q, k, lse,
    kept, keep, q_i, k_i, w, lse_i, the dense loss, its gradients for q_i, k_i and w)."""
    from ray_tpu.ops.flash_attention import pack_keep
    from ray_tpu.ops.lightning_indexer import select

    b, heads, kv_heads, seq, d, index_heads, index_d, topk, dtype, _ = INDEX_LOSS_CASES[case]
    rounded = lambda x: x.astype(dtype).astype(jnp.float32)  # the yardstick sees what the operands hold
    q_i, k_i, w = _indexer_inputs(b, index_heads, seq, index_d)
    q_i, k_i = rounded(q_i), rounded(k_i)
    keys = jax.random.split(jax.random.PRNGKey(4), 2)
    q = rounded(jax.random.normal(keys[0], (b, heads, seq, d), jnp.float32))
    k = rounded(jax.random.normal(keys[1], (b, kv_heads, seq, d), jnp.float32))
    kept = _top_k_set(_scores(q_i, k_i, w), topk)
    keep, lse_i = select(q_i, k_i, w, topk, backend="xla")
    assert bool((pack_keep(kept) == keep).all())
    _, lse = _dense_masked(q, k, k, kept)

    def dense(q_i, k_i, w):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, heads // kv_heads, axis=1)) * d ** -0.5
        p = jax.nn.softmax(jnp.where(kept[:, None], s, -jnp.inf), axis=-1).mean(axis=1)
        log_q = jax.nn.log_softmax(jnp.where(kept, _scores(q_i, k_i, w), -jnp.inf), axis=-1)
        live = kept & (p > 0)
        return jnp.sum(jnp.where(live, p * (jnp.log(jnp.where(live, p, 1.0)) - jnp.where(kept, log_q, 0.0)), 0.0)) / (b * seq)

    want, want_g = jax.jit(jax.value_and_grad(dense, argnums=(0, 1, 2)))(q_i, k_i, w)
    return q, k, lse, kept, keep, q_i, k_i, w, lse_i, want, want_g


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("case", INDEX_LOSS_CASES)
def test_the_index_loss_and_its_gradient_against_the_dense_form(case, backend, monkeypatch):
    import importlib

    from ray_tpu.ops.lightning_indexer import index_loss, selection_counts

    b, heads, kv_heads, seq, d, index_heads, index_d, topk, dtype, tiles = INDEX_LOSS_CASES[case]
    if tiles is not None:
        li = importlib.import_module("ray_tpu.ops.lightning_indexer")
        monkeypatch.setattr(li, "_loss_plan", lambda *shape: li.LossPlan(*tiles[:2], (*tiles, True)[2], 0))
    q, k, lse, kept, keep, q_i, k_i, w, lse_i, want, want_g = _index_loss_yardstick(case)

    # One program for everything the case reads: the loss times `scale`, and its gradient for the indexer's
    # three operands and for the attention's own two.
    def mine(q_i, k_i, w, q, k, scale):
        return scale * index_loss(q.astype(dtype), k.astype(dtype), lse, keep, q_i.astype(dtype), k_i.astype(dtype),
                                  w, lse_i, backend=backend, interpret=True)

    both = jax.jit(jax.value_and_grad(mine, argnums=(0, 1, 2, 3, 4)))
    tol = 2e-5 if dtype == jnp.float32 else TOLERANCE[dtype]
    got, (*got_g, dq, dk) = both(q_i, k_i, w, q, k, 1.0)
    assert float(got) == pytest.approx(float(want), rel=tol) and float(want) > 0.05
    for a, b_ in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=tol * float(jnp.abs(b_).max()) + 1e-9)
    # No gradient reaches the attention's own operands, and a cotangent scales the three.
    assert float(jnp.abs(dq).max()) == float(jnp.abs(dk).max()) == 0.0
    twice = both(q_i, k_i, w, q, k, 2.0)[1][2]
    np.testing.assert_allclose(np.asarray(twice), 2 * np.asarray(got_g[2]), rtol=1e-6)
    counts = selection_counts(keep, tile=128)
    n = seq // 128
    assert float(counts["selected_pairs"]) == float(kept.sum()) and int(counts["tiles"]) == b * n * (n + 1) // 2
    assert float(counts["causal_pairs"]) == b * seq * (seq + 1) / 2 and 1 <= int(counts["live_tiles"]) <= int(counts["tiles"])
    assert int(counts["keys_per_query_min"]) == 1 and int(counts["keys_per_query_max"]) >= topk


def test_the_index_loss_plan_counts_what_a_program_holds_and_cuts_its_tiles_to_the_row():
    """At the Keye cell's shapes the plan keeps the pair's 16 index scores, at the largest pair whose count
    stays under what it may hold with no `vmem_limit_bytes` asked for; the count is from above (the v5e's
    compiler takes 10.75 MiB for that program: `tests/test_aot_v5e.py` compiles it); tiles divide every row."""
    from ray_tpu.ops.flash_attention import KEEP_SPAN, LANES
    from ray_tpu.ops.lightning_indexer import LOSS_VMEM_BYTES, _loss_bytes, _loss_plan

    cell = (32, 4, 16384, 128, 16, 64, 2)
    plan = _loss_plan(*cell)
    assert plan == (256, 256, True, _loss_bytes(256, 256, True, 32, 4, 128, 16, 64, 2))
    assert 10.75 * 2 ** 20 < plan.vmem_bytes <= LOSS_VMEM_BYTES < 16 * 2 ** 20
    assert _loss_bytes(256, 512, True, 32, 4, 128, 16, 64, 2) > LOSS_VMEM_BYTES  # the next pair up holds too much
    assert _loss_bytes(512, 512, False, 32, 4, 128, 16, 64, 2) > 16 * 2 ** 20  # 15.4 MiB alone, not inside a step
    # More indexer heads than any pair keeps the scores of: made twice, at the largest pair that fits.
    wide = _loss_plan(32, 4, 16384, 128, 32, 64, 2)
    assert not wide.keep_scores and wide.vmem_bytes <= LOSS_VMEM_BYTES and wide[:2] == (256, 512)
    for seq, shape in ((512, (4, 2, 512, 64, 4, 64, 4)), (1536, (8, 2, 1536, 64, 4, 32, 4)), (640, (4, 2, 640, 64, 4, 32, 4)),
                       (4096, (32, 8, 4096, 64, 16, 64, 2))):
        plan = _loss_plan(*shape)
        assert seq % plan.tile_q == seq % plan.tile_k == 0 and KEEP_SPAN % plan.tile_k == plan.tile_k % LANES == 0
        assert plan.vmem_bytes <= LOSS_VMEM_BYTES
    assert _loss_plan(4, 2, 640, 64, 4, 32, 4)[:2] == (128, 128)
