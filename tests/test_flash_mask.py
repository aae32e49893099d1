"""A mask by structure in `ops/flash_attention.py` (PR 47): `BlockDiffusion` as the schedules class it and as the
kernels apply it, against the same mask handed over as a packed selection (`keep=pack_keep(dense mask)`), through
`xla_attention`, `blockwise_attention` and, in interpret mode, both pair-streamed kernels; the causal schedules,
which must come out as the parent built them (the seven cells run them); and the live span of keys that both
schedules name for every pair (PR 48), under this mask and under the causal diagonal."""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

fa = importlib.import_module("ray_tpu.ops.flash_attention")
from ray_tpu.ops.flash_attention import (BlockDiffusion, KernelPlan, blockwise_attention, flash_attention,  # noqa: E402
                                         kernel_plan, pack_keep, xla_attention)


def _dense(mask: BlockDiffusion) -> np.ndarray:
    """The issue's table, written out: r a query, c a key of the doubled row."""
    n = 2 * mask.seq
    out = np.zeros((n, n), bool)
    for r in range(n):
        for c in range(n):
            q_blk, k_blk = (r % mask.seq) // mask.block, (c % mask.seq) // mask.block
            if c < mask.seq:
                out[r, c] = k_blk < q_blk if r >= mask.seq else k_blk <= q_blk
            else:
                out[r, c] = r >= mask.seq and k_blk == q_blk
    return out


# ------------------------------------------------------------------ the mask itself
@pytest.mark.parametrize("seq,block,tile_q,tile_k", [
    (64, 4, 16, 32), (64, 8, 32, 16), (48, 4, 16, 16), (40, 4, 16, 16), (64, 16, 16, 32), (24, 3, 8, 16), (32, 1, 8, 8)])
def test_kept_is_the_table_and_every_tile_is_classed_by_what_it_holds(seq, block, tile_q, tile_k):
    mask, n = BlockDiffusion(seq, block), 2 * seq
    dense = _dense(mask)
    rows, cols = np.meshgrid(np.arange(n), np.arange(n), indexing="ij")
    assert np.array_equal(np.asarray(mask.kept(rows, cols)), dense)
    assert np.array_equal(np.asarray(mask.dense(n, n)), dense)
    assert dense.any(axis=1).all()  # every query keeps a key: no row of the softmax is empty
    for r0 in range(0, n, tile_q):
        for c0 in range(0, n, tile_k):
            sub = dense[r0:r0 + tile_q, c0:c0 + tile_k]
            want = fa.WHOLE if sub.all() else fa.CROSSED if sub.any() else fa.EMPTY
            assert mask.tile_class(r0, min(r0 + tile_q, n), c0, min(c0 + tile_k, n)) == want, (r0, c0)


def test_the_cells_row_walks_160_of_512_tile_pairs_and_no_empty_one():
    """(32 on 4, 16,384, 128) under blocks of 4: of 4 n^2 tile pairs (n = L / tile) about n^2 + 2 n are live."""
    mask = BlockDiffusion(8192, 4)
    plan = kernel_plan((1, 32, 16384, 128), mask, kv_heads=4)
    assert plan == (512, 1024, 160, 48, 512, False) and plan.scope == "tiles_160of512"
    assert kernel_plan((1, 32, 16384, 128), mask) == plan  # a mask by structure runs a pair a program whatever the heads
    assert kernel_plan((1, 32, 16384, 128), True, kv_heads=4).tiles_visited == 272  # the causal walk of the same row
    assert fa._streams_pairs(1024, 64, 2, False, False, mask) and not fa._streams_pairs(1024, 64, 2, False, False, True)
    live = sum(mask.tile_class(i * 512, (i + 1) * 512, j * 1024, (j + 1) * 1024) != fa.EMPTY
               for i in range(32) for j in range(16))
    assert live == plan.tiles_visited
    with pytest.raises(AssertionError, match="a pair a program"):
        fa._kernel_blocks(1024, 64, mask)


@pytest.mark.parametrize("seq,block,tile_q,tile_k", [(8192, 4, 512, 1024), (8192, 4, 256, 1024), (2048, 4, 512, 512),
                                                     (1024, 16, 128, 256), (512, 4, 256, 128)])
def test_both_schedules_visit_each_live_pair_once_and_write_each_block_when_it_is_whole(seq, block, tile_q, tile_k):
    mask, n = BlockDiffusion(seq, block), 2 * seq
    plan = KernelPlan(tile_q, tile_k, 0, 0, 0, False)
    live = {(i, j): mask.tile_class(i * tile_q, (i + 1) * tile_q, j * tile_k, (j + 1) * tile_k)
            for i in range(n // tile_q) for j in range(n // tile_k)}
    live = {pair: kind == fa.CROSSED for pair, kind in live.items() if kind != fa.EMPTY}
    i, j, first, crossed, last = fa._fwd_schedule(n, plan, mask)[:5]
    assert sorted(zip(i, j)) == sorted(live) and all(live[pair] == c for pair, c in zip(zip(i, j), crossed))
    assert list(i) == sorted(i) and first.sum() == last.sum() == n // tile_q  # a Q tile's pairs in one run
    for t in range(len(i)):
        assert first[t] == (t == 0 or i[t - 1] != i[t]) and last[t] == (t == len(i) - 1 or i[t + 1] != i[t])
    i, j, due, first, crossed, whole = fa._pair_schedule(n, plan, mask)[:6]
    assert sorted(zip(i, j)) == sorted(live) and all(live[pair] == c for pair, c in zip(zip(i, j), crossed))
    assert list(j) == sorted(j) and whole.sum() == n // tile_q
    for t in range(len(i)):
        assert first[t] == (t == 0 or j[t - 1] != j[t])
        assert whole[t] == (i[t] not in i[t + 1:]) and (not whole[t] or due[t] == i[t])
    assert list(due) == sorted(due)  # the dq block moves on only once it was written


# ------------------------------------------------------------------ a pair's live span of keys (PR 48)
def _kept_of_pair(mask, i, j, tile_q, tile_k):
    rows, cols = np.meshgrid(np.arange(i * tile_q, (i + 1) * tile_q), np.arange(j * tile_k, (j + 1) * tile_k), indexing="ij")
    return rows >= cols if mask is True else np.asarray(mask.kept(rows, cols))


@pytest.mark.parametrize("mask,seq,tile_q,tile_k", [
    (BlockDiffusion(8192, 4), 16384, 512, 1024), (BlockDiffusion(8192, 4), 16384, 256, 1024),
    (BlockDiffusion(2048, 4), 4096, 512, 512), (BlockDiffusion(1024, 16), 2048, 128, 256),
    (BlockDiffusion(512, 4), 1024, 256, 128), (True, 16384, 512, 1024), (True, 2048, 128, 256), (True, 2048, 256, 1024),
    (True, 2048, 512, 256), (True, 4096, 512, 512)])
def test_every_live_span_holds_every_kept_score_of_its_pair_and_no_block_more(mask, seq, tile_q, tile_k):
    """Rows 5-6 of `_fwd_schedule` and 6-7 of `_pair_schedule`: outside its span a pair keeps no score (what the
    kernels skip is what the mask masks), the span's first and last blocks each keep one (it is the shortest such
    run), a pair the mask does not cross carries its whole tile, and both schedules say the same of a pair."""
    plan, blocks = KernelPlan(tile_q, tile_k, 0, 0, 0, False), tile_k // 128
    spans = {}
    for i, j, crossed, first, count in fa._fwd_schedule(seq, plan, mask)[[0, 1, 3, 5, 6]].T:
        spans[i, j] = (first, count)
        if not crossed:
            assert (first, count) == (0, blocks)
            continue
        kept = _kept_of_pair(mask, i, j, tile_q, tile_k).reshape(tile_q, blocks, 128).any(axis=(0, 2))
        assert 0 <= first and 0 < count and first + count <= blocks
        assert not kept[:first].any() and not kept[first + count:].any() and kept[first] and kept[first + count - 1]
    backward = fa._pair_schedule(seq, plan, mask)
    assert {(i, j): (first, count) for i, j, first, count in backward[[0, 1, 6, 7]].T} == spans
    if tile_q == tile_k and mask is True:  # the diagonal's last query sees every key of its own tile
        assert fa._short_spans(backward, tile_k) == ()


def test_the_walks_score_1152_of_1280_key_blocks_in_the_sdar_row_2112_of_2176_in_keyes_and_all_of_them_at_square_tiles():
    def scopes(shape, mask, **kw):
        plan = kernel_plan(shape, mask, **kw)
        return tuple("/".join(fa._walk_scope(schedule(shape[2], plan, mask), shape[2], plan.tile_q, plan.tile_k))
                     for schedule in (fa._fwd_schedule, fa._pair_schedule))

    assert scopes((1, 32, 16384, 128), BlockDiffusion(8192, 4), kv_heads=4) == ("tiles_160of512/keys_1152of1280",) * 2
    assert scopes((1, 32, 16384, 128), True, kv_heads=4, keep=True) == ("tiles_272of512/keys_2112of2176",) * 2
    assert scopes((2, 20, 4096, 256), True)[1] == "tiles_36of64/keys_144of144"  # GLM's backward: nothing to drop
    assert scopes((1, 8, 8192, 256), True, keep=True) == ("tiles_136of256/keys_544of544",) * 2
    # The lengths the backward pass has a form for: a half at these tiles, none where the tiles are square.
    plan = kernel_plan((1, 32, 16384, 128), BlockDiffusion(8192, 4), kv_heads=4)
    assert fa._short_spans(fa._pair_schedule(16384, plan, BlockDiffusion(8192, 4)), 1024) == (512,)
    assert fa._short_spans(fa._pair_schedule(16384, plan, True), 1024) == (512,)
    assert fa._short_spans(fa._pair_schedule(4096, kernel_plan((2, 20, 4096, 256)), True), 512) == ()


# ------------------------------------------------------------------ the causal schedules are the parent's
def _parents_fwd_schedule(seq, tile_q, tile_k, causal):
    n_q, n_k = seq // tile_q, seq // tile_k
    steps = []
    for i in range(n_q):
        diag, end = fa._diag_and_end(i, tile_q, tile_k, n_k, True) if causal else (n_k, n_k)
        steps += [(i, j, j == 0, diag <= j < end, j == end - 1) for j in range(end)]
    return np.asarray(steps, np.int32).T


def _parents_pair_schedule(seq, tile_q, tile_k, causal):
    n_q, n_k = seq // tile_q, seq // tile_k
    pairs = []
    for j in range(n_k):
        diag, end = fa._diag_and_end(j, tile_k, tile_q, n_q, True) if causal else (0, 0)
        pairs += [(i, j, i == diag, diag <= i < end) for i in range(diag, n_q)]
    whole_at = {i: t for t, (i, *_) in enumerate(pairs)}
    steps, due = [], 0
    for t, (i, j, first, masked) in enumerate(pairs):
        steps.append((i, j, due, first, masked, whole_at[i] == t))
        due = min(due + (whole_at[due] == t), n_q - 1)
    return np.asarray(steps, np.int32).T


def _parents_counts(seq, tile_q, tile_k, causal):
    n_k, visited, masked = seq // tile_k, 0, 0
    for i in range(seq // tile_q):
        diag, end = fa._diag_and_end(i, tile_q, tile_k, n_k, True) if causal else (n_k, n_k)
        visited, masked = visited + end, masked + end - diag
    return visited, masked


# The seven cells' attention calls (rows a chip, heads, positions, head_dim; key/value heads; a selection), and the plan
# each ran at PR 46 (`tests/test_aot_v5e.py`, `tests/test_flash_pairs.py`, `tests/benchmark/test_benchmark_keye_vl2.py`).
CELLS = {
    "gpt2-medium.resident": ((8, 16, 1024, 64), None, False, (512, 512, 3, 2, 4, True)),
    "gpt2-medium.fed": ((8, 16, 1024, 64), None, False, (512, 512, 3, 2, 4, True)),
    "gpt2-xl-fsdp4.fed": ((4, 25, 1024, 64), None, False, (512, 512, 3, 2, 4, True)),
    "olmoe-1b-7b-l1.fed4k": ((2, 16, 4096, 128), None, False, (512, 512, 36, 8, 64, False)),
    "lfm2-24b-a2b-ep8-l5.fed4k": ((8, 32, 4096, 64), 8, False, (512, 1024, 20, 8, 32, False)),
    "glm-4.7-flash-ep8-l5.fed4k": ((2, 20, 4096, 256), None, False, (512, 512, 36, 8, 64, False)),
    "keye-vl-2.0-30b-a3b-ep8.fed16k": ((1, 32, 16384, 128), 4, True, (512, 1024, 272, 32, 512, False)),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_causal_schedules_and_counts_of_the_seven_cells_are_the_parents(cell):
    shape, kv_heads, keep, _ = CELLS[cell]
    for causal in (True, False):
        plan = kernel_plan(shape, causal, kv_heads=kv_heads, keep=keep)
        seq = shape[2]
        assert (plan.tiles_visited, plan.tiles_masked) == _parents_counts(seq, plan.tile_q, plan.tile_k, causal)
        for tile_q in (plan.tile_q, plan.tile_q // 2):  # the forward may halve its Q tile (`_fwd_pairs_plan`)
            mine = fa._fwd_schedule(seq, plan._replace(tile_q=tile_q), causal)
            theirs = _parents_fwd_schedule(seq, tile_q, plan.tile_k, causal)
            assert mine.dtype == theirs.dtype and mine[:5].tobytes() == theirs.tobytes()  # the rows the parent had
        mine, theirs = fa._pair_schedule(seq, plan, causal), _parents_pair_schedule(seq, plan.tile_q, plan.tile_k, causal)
        assert mine.dtype == theirs.dtype and mine[:6].tobytes() == theirs.tobytes()


def test_the_cells_plans_are_those_of_pr_46():
    for cell, (shape, kv_heads, keep, pinned) in CELLS.items():
        if cell.startswith("lfm2"):  # its key/value heads are repeated by the model: the call sees 32 equal heads
            assert kernel_plan(shape) == (512, 512, 36, 8, 64, False)
        else:
            assert kernel_plan(shape, kv_heads=kv_heads, keep=keep) == pinned, cell


# ------------------------------------------------------------------ values and gradients
def _operands(seq, heads, kv_heads, d, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, w = (jax.random.normal(key, (1, heads, 2 * seq, d), jnp.float32) for key in keys[:2])
    k, v = (jax.random.normal(key, (1, kv_heads, 2 * seq, d), jnp.float32) for key in keys[2:])
    return q, k, v, w


def _value_and_grads(f, q, k, v, w):
    return f(q, k, v), jax.grad(lambda q, k, v: (f(q, k, v) * w).sum(), argnums=(0, 1, 2))(q, k, v)


# `block` 1: a noised query of the first block keeps one key, its own (a row of a crossed tile with one kept score).
# (128, 256) and (256, 1024): a crossed pair's live span is a half of its K tile, the first or the second (PR 48); with
# equal heads too (the forward then takes them all a program, each with its own key/value head).
@pytest.mark.parametrize("form,block,tiles,seq,kv_heads", [
    ("xla", 4, None, 256, 2), ("xla", 1, None, 256, 2), ("blockwise", 4, None, 256, 2),
    ("pallas", 4, (128, 128), 256, 2), ("pallas", 4, (128, 256), 256, 2), ("pallas", 4, (256, 128), 256, 2),
    ("pallas", 1, (128, 256), 256, 2), ("pallas", 4, (256, 1024), 1024, 2), ("pallas", 4, (128, 256), 256, 4)])
def test_the_mask_by_structure_is_the_packed_selection_of_its_dense_form(form, block, tiles, seq, kv_heads):
    mask = BlockDiffusion(seq, block)
    q, k, v, w = _operands(seq, 4, kv_heads, 32)
    keep = pack_keep(jnp.asarray(_dense(mask)))[None]
    if block == 1:
        assert _dense(mask)[seq].sum() == 1
    want, want_g = _value_and_grads(lambda q, k, v: xla_attention(q, k, v, causal=False, keep=keep), q, k, v, w)
    if form == "xla":
        f = lambda q, k, v: xla_attention(q, k, v, causal=mask)
    elif form == "blockwise":
        f = lambda q, k, v: blockwise_attention(q, *fa._repeat_kv(q, k, v), causal=mask, block_k=128)
    else:
        f = lambda q, k, v: flash_attention(q, k, v, causal=mask, backend="pallas", interpret=True,
                                            block_q=tiles[0], block_k=tiles[1])
    got, got_g = _value_and_grads(f, q, k, v, w)
    assert bool(jnp.isfinite(got).all()) and all(bool(jnp.isfinite(g).all()) for g in got_g)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    for mine, theirs in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(mine), np.asarray(theirs), atol=5e-5)


@pytest.mark.parametrize("seq,tiles", [(256, (128, 256)), (1024, (256, 1024))])
def test_the_kernels_row_statistics_under_the_mask_are_the_xla_forms(seq, tiles):
    mask = BlockDiffusion(seq, 4)
    q, k, v, _ = _operands(seq, 4, 2, 32, seed=3)
    _, want = xla_attention(q, k, v, causal=mask, return_lse=True)
    _, got = flash_attention(q, k, v, causal=mask, backend="pallas", interpret=True, block_q=tiles[0], block_k=tiles[1],
                             return_lse=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
