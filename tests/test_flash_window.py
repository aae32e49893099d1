"""The second mask by structure in `ops/flash_attention.py` (PR 61): `SlidingWindow` as the schedules class it and
as the kernels apply it, against a dense masked softmax written from the two comparisons of positions, through
`xla_attention`, `blockwise_attention` and, in interpret mode, both pair-streamed kernels; the pair that the mask
crosses on two sides (the diagonal above, the window's edge below), whose live keys lie in the middle of its K tile;
the schedules' counts at the Trinity cell's row by hand; and a window at least the row, which is `causal=True` to the
bit. (`tests/test_flash_mask.py` holds the first description and the causal schedules to the parent's.)"""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

fa = importlib.import_module("ray_tpu.ops.flash_attention")
from ray_tpu.ops.flash_attention import (KernelPlan, SlidingWindow, blockwise_attention, flash_attention,  # noqa: E402
                                         kernel_plan, xla_attention)


def _dense(seq: int, window: int) -> np.ndarray:
    """The issue's two comparisons, written out: query i keeps key j where 0 <= i - j < window."""
    out = np.zeros((seq, seq), bool)
    for i in range(seq):
        for j in range(seq):
            out[i, j] = 0 <= i - j < window
    return out


# ------------------------------------------------------------------ the mask itself
@pytest.mark.parametrize("seq,window,tile_q,tile_k", [
    (64, 16, 16, 32), (64, 8, 32, 16), (64, 5, 16, 16), (64, 16, 16, 16), (64, 24, 8, 32), (48, 1, 16, 16),
    (64, 64, 16, 32), (64, 100, 16, 32), (40, 7, 8, 8)])
def test_kept_is_the_two_comparisons_and_every_tile_is_classed_by_what_it_holds(seq, window, tile_q, tile_k):
    mask, dense = SlidingWindow(window), _dense(seq, window)
    rows, cols = np.meshgrid(np.arange(seq), np.arange(seq), indexing="ij")
    assert np.array_equal(np.asarray(mask.kept(rows, cols)), dense)
    assert np.array_equal(np.asarray(mask.dense(seq, seq)), dense)
    assert dense.diagonal().all()  # every query keeps its own key: no row of the softmax is empty
    assert np.array_equal(dense.sum(axis=1), np.minimum(np.arange(seq) + 1, window))
    for r0 in range(0, seq, tile_q):
        for c0 in range(0, seq, tile_k):
            sub = dense[r0:r0 + tile_q, c0:c0 + tile_k]
            want = fa.WHOLE if sub.all() else fa.CROSSED if sub.any() else fa.EMPTY
            assert mask.tile_class(r0, min(r0 + tile_q, seq), c0, min(c0 + tile_k, seq)) == want, (r0, c0)
    assert hash(mask) == hash(SlidingWindow(window)) and mask == SlidingWindow(window)  # a static argument of a jit


def test_the_cells_row_walks_90_of_512_tile_pairs_and_scores_600_of_720_key_blocks():
    """(32 on 4, 16,384, 128) under a window of 2,048 at 512 x 1,024 tiles. By hand: Q tile i holds rows 512 i ..
    512 i + 511 and needs keys 512 i - 2,047 .. 512 i + 511, which lie in K tiles floor((512 i - 2,047) / 1,024) ..
    floor(i / 2): one for i = 0, 1, two for i = 2, 3, three from i = 4 on: 2 + 4 + 28 x 3 = 90. Of a Q tile's three
    the first is cut by the window's edge (where i is odd its live keys are the tile's last 511, four blocks of 128 at
    the tile's end; where i is even all but its first key, every block), the second is whole, and the last is cut by
    the diagonal (where i is even its first 512 keys, four blocks; where i is odd every block): 14 x (8 + 8 + 4) + 14 x
    (4 + 8 + 8) + the first four Q tiles' 4 + 8 + 12 + 16 = 600 of the 90 x 8 = 720 blocks walked."""
    mask = SlidingWindow(2048)
    plan = kernel_plan((1, 32, 16384, 128), mask, kv_heads=4)
    assert plan == (512, 1024, 90, 60, 512, False) and plan.scope == "tiles_90of512"
    assert kernel_plan((1, 32, 16384, 128), mask) == plan  # a mask by structure runs a pair a program whatever the heads
    assert kernel_plan((1, 32, 16384, 128), True, kv_heads=4).tiles_visited == 272  # the triangle of the same row
    forward, backward = fa._fwd_schedule(16384, plan, mask), fa._pair_schedule(16384, plan, mask)
    assert "/".join(fa._walk_scope(forward, 16384, 512, 1024)) == "tiles_90of512/keys_600of720"
    assert "/".join(fa._walk_scope(backward, 16384, 512, 1024)) == "tiles_90of512/keys_600of720"
    assert sorted(zip(*forward[:2])) == sorted(zip(*backward[:2]))  # the backward's walk by K tile: the same pairs
    assert fa._short_spans(backward, 1024) == (512,)  # one more static form of the pair, as the diagonal's has
    # the pairs by hand
    want = {(i, j) for i in range(32) for j in range(16) if 1024 * j <= 512 * i + 511 and 1024 * j + 1023 >= 512 * i - 2047}
    assert set(zip(*forward[:2])) == want and len(want) == 90
    spans = {(i, j): (first, count) for i, j, first, count in forward[[0, 1, 5, 6]].T}
    assert spans[5, 0] == (4, 4) and spans[5, 1] == spans[5, 2] == (0, 8)  # odd: the edge leaves the first tile its last 512 keys
    assert spans[4, 0] == spans[4, 1] == (0, 8) and spans[4, 2] == (0, 4)  # even: the diagonal leaves the last its first 512
    assert sum(count for _, count in spans.values()) == 600
    # the kept scores of a head: a triangle of 2,048 and then 2,048 a row
    kept = 2048 * 2049 // 2 + (16384 - 2048) * 2048
    assert kept == 31_458_304 and kept / (16384 * 16385 // 2) == pytest.approx(0.2344, abs=1e-4)


@pytest.mark.parametrize("seq,window,tile_q,tile_k", [
    (16384, 2048, 512, 1024), (16384, 2048, 256, 1024), (4096, 1000, 512, 512), (2048, 100, 128, 256),
    (2048, 256, 256, 256), (1024, 300, 256, 128), (1024, 2000, 128, 256)])
def test_both_schedules_walk_the_band_and_nothing_else(seq, window, tile_q, tile_k):
    """Each live pair once, no empty one; a Q tile's pairs in one run of the forward (whose first K tile is not tile
    0 once the window has left it); in the backward, K tiles in turn, each with the Q tiles of the band alone, every
    dq block written in the step its tile becomes whole and the blocks due in their order."""
    mask = SlidingWindow(window)
    plan = KernelPlan(tile_q, tile_k, 0, 0, 0, False)
    n_q, n_k = seq // tile_q, seq // tile_k
    live = {(i, j): mask.tile_class(i * tile_q, (i + 1) * tile_q, j * tile_k, (j + 1) * tile_k)
            for i in range(n_q) for j in range(n_k)}
    live = {pair: kind == fa.CROSSED for pair, kind in live.items() if kind != fa.EMPTY}
    dense_live = {(i, j) for i in range(n_q) for j in range(n_k)
                  if j * tile_k <= (i + 1) * tile_q - 1 and i * tile_q - ((j + 1) * tile_k - 1) < window}
    assert set(live) == dense_live
    i, j, first, crossed, last = fa._fwd_schedule(seq, plan, mask)[:5]
    assert sorted(zip(i, j)) == sorted(live) and all(live[pair] == c for pair, c in zip(zip(i, j), crossed))
    assert list(i) == sorted(i) and first.sum() == last.sum() == n_q
    for t in range(len(i)):
        assert first[t] == (t == 0 or i[t - 1] != i[t]) and last[t] == (t == len(i) - 1 or i[t + 1] != i[t])
    if window + tile_q <= seq - tile_k:
        assert j[first == 1].max() > 0  # a Q tile whose first K tile is not tile 0
    i, j, due, first, crossed, whole = fa._pair_schedule(seq, plan, mask)[:6]
    assert sorted(zip(i, j)) == sorted(live) and all(live[pair] == c for pair, c in zip(zip(i, j), crossed))
    assert list(j) == sorted(j) and first.sum() == n_k and whole.sum() == n_q
    written = [int(due[t]) for t in range(len(i)) if whole[t]]
    assert written == list(range(n_q)) and all(i[t] == due[t] for t in range(len(i)) if whole[t])
    for t in range(len(i)):  # a step's dq block is the next to become whole: no block is revisited after it was written
        assert due[t] >= max([d for d, w in zip(due[:t], whole[:t]) if w], default=-1)


def _kept_of_pair(mask, i, j, tile_q, tile_k):
    rows, cols = np.meshgrid(np.arange(i * tile_q, (i + 1) * tile_q), np.arange(j * tile_k, (j + 1) * tile_k), indexing="ij")
    return np.asarray(mask.kept(rows, cols))


@pytest.mark.parametrize("seq,window,tile_q,tile_k", [
    (16384, 2048, 512, 1024), (16384, 2048, 256, 1024), (4096, 1000, 512, 512), (2048, 100, 128, 1024),
    (2048, 300, 128, 1024), (2048, 256, 256, 256), (1024, 130, 256, 512)])
def test_every_live_span_holds_every_kept_score_of_its_pair_and_no_block_more(seq, window, tile_q, tile_k):
    """The window's lower edge leaves a pair its live keys at the end of the K tile, the diagonal at the start, and a
    window narrower than the tile, which cuts one pair on both sides, in the middle: the span is asked of the mask a
    block at a time and is one run wherever it lies. Both schedules say the same of a pair."""
    mask = SlidingWindow(window)
    plan, blocks = KernelPlan(tile_q, tile_k, 0, 0, 0, False), tile_k // 128
    spans, places = {}, set()
    for i, j, crossed, first, count in fa._fwd_schedule(seq, plan, mask)[[0, 1, 3, 5, 6]].T:
        spans[i, j] = (first, count)
        if not crossed:
            assert (first, count) == (0, blocks)
            continue
        kept = _kept_of_pair(mask, i, j, tile_q, tile_k).reshape(tile_q, blocks, 128).any(axis=(0, 2))
        assert 0 <= first and 0 < count and first + count <= blocks
        assert not kept[:first].any() and not kept[first + count:].any() and kept[first:first + count].all()
        places.add(("start" if first == 0 else "") + ("end" if first + count == blocks else "") or "middle")
    backward = fa._pair_schedule(seq, plan, mask)
    assert {(i, j): (first, count) for i, j, first, count in backward[[0, 1, 6, 7]].T} == spans
    if window + tile_q + 128 < tile_k:
        assert "middle" in places  # cut on both sides


# ------------------------------------------------------------------ values and gradients
def _operands(seq, heads, kv_heads, d, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, w = (jax.random.normal(key, (1, heads, seq, d), jnp.float32) for key in keys[:2])
    k, v = (jax.random.normal(key, (1, kv_heads, seq, d), jnp.float32) for key in keys[2:])
    return q, k, v, w


def _value_and_grads(f, q, k, v, w):
    return f(q, k, v), jax.grad(lambda q, k, v: (f(q, k, v) * w).sum(), argnums=(0, 1, 2))(q, k, v)


def _dense_softmax(window):
    """A masked softmax with no kernel and no mask object: scores, the two comparisons, softmax, values."""
    def f(q, k, v):
        group, seq = q.shape[1] // k.shape[1], q.shape[2]
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(q.shape[-1])
        i, j = jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]
        s = jnp.where((i - j >= 0) & (i - j < window), s, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)
    return f


# Windows smaller than a tile (50 under 128 x 128; 100 under a K tile of 512: one pair cut on both sides), equal to one
# (128), no multiple of one (200, 300), and at least the row (512, 600). Grouped heads 4 on 2, 8 on 1, and equal heads.
@pytest.mark.parametrize("form,window,tiles,seq,heads,kv_heads", [
    ("xla", 100, None, 256, 4, 2), ("xla", 1, None, 256, 4, 2), ("blockwise", 100, None, 256, 4, 2),
    ("blockwise", 300, None, 512, 4, 2),
    ("pallas", 50, (128, 128), 256, 4, 2), ("pallas", 128, (128, 128), 512, 4, 2), ("pallas", 200, (128, 128), 512, 4, 2),
    ("pallas", 100, (128, 512), 512, 4, 2), ("pallas", 300, (128, 256), 512, 8, 1), ("pallas", 200, (256, 128), 512, 4, 4),
    ("pallas", 300, (256, 512), 1024, 4, 2), ("pallas", 600, (128, 256), 512, 4, 2)])
def test_the_window_is_the_dense_masked_softmax_forward_and_in_the_three_gradients(form, window, tiles, seq, heads, kv_heads):
    mask = SlidingWindow(window)
    q, k, v, w = _operands(seq, heads, kv_heads, 32)
    want, want_g = _value_and_grads(_dense_softmax(window), q, k, v, w)
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


@pytest.mark.parametrize("form,tiles,kv_heads", [("xla", None, 2), ("blockwise", None, 4), ("pallas", (128, 256), 2),
                                                  ("pallas", (128, 128), 1)])
def test_a_window_of_at_least_the_row_is_the_causal_diagonal_to_the_bit(form, tiles, kv_heads):
    seq = 512
    q, k, v, w = _operands(seq, 4, kv_heads, 32, seed=1)

    def f(causal):
        if form == "xla":
            return lambda q, k, v: xla_attention(q, k, v, causal=causal)
        if form == "blockwise":
            return lambda q, k, v: blockwise_attention(q, *fa._repeat_kv(q, k, v), causal=causal, block_k=128)
        return lambda q, k, v: flash_attention(q, k, v, causal=causal, backend="pallas", interpret=True,
                                               block_q=tiles[0], block_k=tiles[1])

    want, want_g = _value_and_grads(f(True), q, k, v, w)
    for window in (seq, seq + 77):
        got, got_g = _value_and_grads(f(SlidingWindow(window)), q, k, v, w)
        assert np.array_equal(np.asarray(got), np.asarray(want))
        assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(got_g, want_g))
    if form == "pallas":  # and the same walk: the diagonal's pairs, crossed where it crosses
        plan = kernel_plan((1, 4, seq, 32), True, *tiles, kv_heads=1)
        assert np.array_equal(fa._fwd_schedule(seq, plan, SlidingWindow(seq)), fa._fwd_schedule(seq, plan, True))
        assert np.array_equal(fa._pair_schedule(seq, plan, SlidingWindow(seq)), fa._pair_schedule(seq, plan, True))


@pytest.mark.parametrize("seq,window,tiles", [(512, 100, (128, 512)), (1024, 300, (256, 1024))])
def test_the_kernels_row_statistics_under_the_window_are_the_xla_forms(seq, window, tiles):
    mask = SlidingWindow(window)
    q, k, v, _ = _operands(seq, 4, 2, 32, seed=3)
    _, want = xla_attention(q, k, v, causal=mask, return_lse=True)
    _, got = flash_attention(q, k, v, causal=mask, backend="pallas", interpret=True, block_q=tiles[0], block_k=tiles[1],
                             return_lse=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ------------------------------------------------------------------ groups of 7 under a window of 4,096 (PR 70)
def test_the_smallthinker_cells_row_walks_140_of_512_tile_pairs_and_scores_1008_of_1120_key_blocks():
    """(28 on 4, 16,384, 128) under a window of 4,096 at 512 x 1,024 tiles. By hand: Q tile i needs keys 512 i - 4,095
    .. 512 i + 511, which lie in K tiles floor((512 i - 4,095) / 1,024) .. floor(i / 2): one for i = 0, 1, two for 2, 3,
    three for 4, 5, four for 6, 7 and five from i = 8 on: 2 + 4 + 6 + 8 + 24 x 5 = 140. Of a Q tile's five the first is
    cut by the window's edge (odd i: its last 511 keys, four blocks; even i: all but its first key, every block), the
    three in the middle are whole, and the last is cut by the diagonal (even i: four blocks; odd i: every block): 12 x
    (8 + 24 + 4) + 12 x (4 + 24 + 8) + the first eight Q tiles' 4 + 8 + 12 + 16 + 20 + 24 + 28 + 32 = 1,008 of the 140 x
    8 = 1,120 blocks walked: 1.11 walked a block scored where Trinity-Mini's window of 2,048 reads 1.2, and 84 of the 140
    pairs whole where 30 of its 90 are. The group of 7: one key/value head's whole group a program, 4 programs a call."""
    mask = SlidingWindow(4096)
    plan = kernel_plan((1, 28, 16384, 128), mask, kv_heads=4)
    assert plan[:3] == (512, 1024, 140) and plan.scope == "tiles_140of512"
    assert plan.tiles_masked == 56  # crossed: every Q tile's diagonal pair (32) and, from i = 8 on, the edge's (24)
    forward, backward = fa._fwd_schedule(16384, plan, mask), fa._pair_schedule(16384, plan, mask)
    assert "/".join(fa._walk_scope(forward, 16384, 512, 1024)) == "tiles_140of512/keys_1008of1120"
    assert "/".join(fa._walk_scope(backward, 16384, 512, 1024)) == "tiles_140of512/keys_1008of1120"
    want = {(i, j) for i in range(32) for j in range(16) if 1024 * j <= 512 * i + 511 and 1024 * j + 1023 >= 512 * i - 4095}
    assert set(zip(*forward[:2])) == want and len(want) == 140
    spans = {(i, j): (first, count) for i, j, first, count in forward[[0, 1, 5, 6]].T}
    assert spans[9, 0] == (4, 4) and spans[9, 1] == spans[9, 2] == spans[9, 3] == spans[9, 4] == (0, 8)
    assert spans[8, 0] == spans[8, 3] == (0, 8) and spans[8, 4] == (0, 4)
    assert sum(count for _, count in spans.values()) == 1008
    kept = 4096 * 4097 // 2 + (16384 - 4096) * 4096
    assert kept == 58_722_304 and kept / (16384 * 16385 // 2) == pytest.approx(0.4375, abs=1e-4)
    # the forward's program: 28, 14, 7 or 1 heads are admitted at group 7, and the whole group is what fits
    assert fa._fwd_pairs_plan(7, 28, 128, 2, plan) == (7, 512)
    assert fa._fwd_pairs_plan(7, 28, 128, 2, kernel_plan((1, 28, 16384, 128), True, kv_heads=4)) == (7, 512)


@pytest.mark.parametrize("check", [test_both_schedules_walk_the_band_and_nothing_else,
                                   test_every_live_span_holds_every_kept_score_of_its_pair_and_no_block_more])
def test_the_schedules_hold_at_a_window_of_4096_on_16384(check):
    check(16384, 4096, 512, 1024)
    check(16384, 4096, 256, 1024)


# heads, key/value heads, and the VMEM a forward program may hold (None: the module's), which decides the heads a
# program takes among those admitted at group 7: a key/value head's whole group, two groups with their two key/value
# heads, or a head a program beside six others on the same key/value head (`parts` = 7).
@pytest.mark.parametrize("heads,kv_heads,vmem,planned,window,tiles", [
    (7, 1, None, 7, 200, (128, 128)), (14, 2, None, 14, None, (128, 256)), (14, 2, 4_000_000, 7, 200, (128, 128)),
    (14, 2, 2_000_000, 1, None, (128, 256)), (28, 4, None, 28, 200, (128, 128))])
def test_groups_of_seven_are_the_dense_masked_softmax_forward_and_in_dq_dk_dv(monkeypatch, heads, kv_heads, vmem, planned,
                                                                             window, tiles):
    """28 on 4 as it is: no key/value head repeated, no group padded to 8. `dk`, `dv` of a key/value head are its seven
    query heads' sums (`_flash_pairs_bwd`'s reshape by the group), whatever the forward's program took."""
    seq = 512
    if vmem is not None:
        monkeypatch.setattr(fa, "FWD_PAIRS_VMEM_BYTES", vmem)
    mask = True if window is None else SlidingWindow(window)
    plan = kernel_plan((1, heads, seq, 32), mask, *tiles, kv_heads=kv_heads)
    assert fa._fwd_pairs_plan(7, heads, 32, 4, plan)[0] == planned
    q, k, v, w = _operands(seq, heads, kv_heads, 32, seed=5)
    want, want_g = _value_and_grads(_dense_softmax(seq if window is None else window), q, k, v, w)
    f = lambda q, k, v: flash_attention(q, k, v, causal=mask, backend="pallas", interpret=True,  # noqa: E731
                                        block_q=tiles[0], block_k=tiles[1])
    got, got_g = _value_and_grads(f, q, k, v, w)
    assert got_g[1].shape == got_g[2].shape == (1, kv_heads, seq, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    for mine, theirs in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(mine), np.asarray(theirs), atol=1e-4)
    text = jax.jit(f).lower(q, k, v).as_text(debug_info=True)
    assert f"group_{planned}/flash_fwd" in text
