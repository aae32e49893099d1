"""`ops/flash_attention.py`, a (Q tile, K tile) pair a program: the pair-streamed backward (PR 39) and forward
(PR 42, 44), a packed selection of keys (`keep`), grouped heads, and the schedules they walk (interpret mode on
CPU). The whole-head and loop forms are `tests/test_flash_attention.py`'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flash_residuals
from attention_yardsticks import TOLERANCE, _dense_masked, _dense_yardstick, _kernel_against_xla, _selection
from ray_tpu.ops.flash_attention import flash_attention, kernel_plan, xla_attention


@pytest.fixture
def every_head_is_streamed(monkeypatch):
    """The backward pass of the loop form as it runs for heads above 4096 x 128
    (bf16): a program a (Q tile, K tile) pair, every operand streamed."""
    import importlib

    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "LONG_HEAD_BYTES", -1)
    monkeypatch.setattr(fa, "MAX_UNROLLED_HEAD_BYTES", 0)
    return fa


@pytest.mark.parametrize("seq,head_dim,causal,block_q,block_k,dtype", [
    (1024, 256, True, 256, 256, jnp.float32),  # the GLM head's width: 10 of 16 pairs, 4 masked
    (1024, 256, True, 256, 256, jnp.bfloat16),
    (1024, 256, False, 256, 256, jnp.float32),  # nothing skipped: dq whole at the last K tile only
    (1024, 64, True, 128, 256, jnp.float32),  # tiles that are not square, both ways
    (1024, 64, True, 256, 128, jnp.float32),
    (1024, 128, False, 256, 128, jnp.float32),
    (512, 256, True, 512, 512, jnp.float32),  # one pair a head
])
def test_the_pair_streamed_backward_matches_xla(every_head_is_streamed, seq, head_dim, causal, block_q,
                                                block_k, dtype):
    plan = kernel_plan((1, 2, seq, head_dim), causal, block_q, block_k, dtype=dtype)
    assert (plan.tile_q, plan.tile_k, plan.unrolled) == (block_q, block_k, False)
    errs = _kernel_against_xla(seq, head_dim, causal, dtype, block_q=block_q, block_k=block_k)
    assert max(errs.values()) <= TOLERANCE[dtype], (plan, errs)


def test_the_pair_schedule_visits_each_pair_once_and_writes_each_dq_tile_when_it_is_whole():
    import importlib

    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    for seq, tq, tk, causal in ((4096, 512, 512, True), (4096, 512, 512, False), (2048, 256, 512, True),
                                (2048, 512, 256, True), (1024, 128, 256, False)):
        plan = kernel_plan((1, 1, seq, 256), causal, tq, tk)
        i, j, due, first, masked, whole = fa._pair_schedule(seq, plan, causal)[:6]
        n_q, n_k = seq // tq, seq // tk
        pairs = list(zip(i.tolist(), j.tolist()))
        assert len(pairs) == len(set(pairs)) == plan.tiles_visited and int(masked.sum()) == plan.tiles_masked
        want = {(a, b) for a in range(n_q) for b in range(n_k) if not causal or b * tk < (a + 1) * tq}
        assert set(pairs) == want
        assert j.tolist() == sorted(j.tolist()) and int(first.sum()) == n_k  # K tiles in turn
        # Every K tile's pairs end at the last Q tile, where dk and dv are written.
        assert all(i[t] == n_q - 1 for t in range(len(pairs)) if t + 1 == len(pairs) or j[t + 1] != j[t])
        # A dq tile is written once, in its last pair, into the block the step's output is;
        # that block never changes before it has been written, and never comes back after.
        assert int(whole.sum()) == n_q and all(due[t] == i[t] for t in range(len(pairs)) if whole[t])
        assert all(t == max(u for u, a in enumerate(i) if a == i[t]) for t in range(len(pairs)) if whole[t])
        assert due.tolist() == sorted(due.tolist())
        assert all(whole[t] for t in range(len(pairs) - 1) if due[t + 1] != due[t])
    assert kernel_plan((2, 20, 4096, 256), True) == (512, 512, 36, 8, 64, False)  # glm-4.7-flash: 36 pairs a head



# ----------------------------------------------------------------------------- a selection of keys (PR 42)
SELECTED = (2, 4, 2, 64, ())  # the shape of the cases that name none


@pytest.mark.parametrize("keys", [64, 128, 4096, 4096 + 128, 3 * 4096])
def test_a_selection_packs_to_a_bit_a_pair_and_back(keys):
    from ray_tpu.ops.flash_attention import KEEP_SPAN, pack_keep, unpack_keep

    mask = jax.random.bernoulli(jax.random.PRNGKey(keys), 0.5, (2, 3, keys))
    packed = pack_keep(mask)
    assert packed.dtype == jnp.int32 and packed.shape == (2, 3, -(-keys // KEEP_SPAN) * 128)
    assert bool((unpack_keep(packed, keys) == mask).all())
    # Bit b of word [span * 128 + lane] is key span * 4096 + b * 128 + lane: the sign bit too.
    one = jnp.zeros((1, 2 * KEEP_SPAN), bool).at[0, KEEP_SPAN + 31 * 128 + 5].set(True)
    assert int(pack_keep(one)[0, 128 + 5]) == -(2 ** 31) and int((pack_keep(one) != 0).sum()) == 1


# (batch, heads, key/value heads, head_dim, queries that keep no key at all) of `_selection`; None: the fixture's.
# The pair-streamed forward takes a key/value head's whole group a program (PR 44): groups of 1, 2 and 8,
# heads of 64, 128 and 256, one and three K tiles a Q tile.
@pytest.mark.parametrize("backend,blocks,shape", [
    ("xla", {}, None), ("pallas", {"block_q": 128, "block_k": 128}, None), ("pallas", {"block_q": 384, "block_k": 384}, None),
    ("pallas", {"block_q": 384, "block_k": 384}, (1, 8, 1, 128, ())),
    ("pallas", {"block_q": 128, "block_k": 128}, (1, 2, 2, 256, ())),
    ("pallas", {"block_q": 128, "block_k": 128}, (1, 8, 1, 64, ())),
    ("pallas", {"block_q": 384, "block_k": 384}, (1, 4, 2, 64, (5, 300))),
    ("pallas", {"block_q": 128, "block_k": 128}, (1, 2, 2, 128, (0, 383))),
    ("xla", {}, (1, 4, 2, 64, (5, 300)))],
    ids=["xla", "pallas-128", "pallas-384x128", "group8-d128-384x128", "group1-d256-128", "group8-d64-128",
         "group2-no-key-384x128", "group1-no-key-128", "xla-no-key"])
def test_keep_forward_and_backward_against_a_dense_masked_softmax(backend, blocks, shape):
    from ray_tpu.ops.flash_attention import NEG_INF, pack_keep

    shape = shape or SELECTED
    q, k, v, mask = _selection(*shape[:4], none_at=shape[4])
    want_o, want_lse, want = _dense_yardstick(*shape)
    keep = pack_keep(mask)
    attn = lambda q, k, v: flash_attention(q, k, v, keep=keep, return_lse=True, backend=backend,
                                           interpret=True, **blocks)
    some = np.asarray(mask.any(axis=-1))[:, None]  # (batch, 1, queries): the rows that keep a key

    def loss(q, k, v):
        o, lse = attn(q, k, v)
        return (o ** 2).sum(), (o, lse)

    # The gradient of a row that keeps no key at all is not defined, the softmax of nothing: the forward pass alone.
    if some.all():
        (_, (o, lse)), got = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    else:
        o, lse = jax.jit(attn)(q, k, v)
    np.testing.assert_allclose(np.where(some[..., None], o, 0), np.where(some[..., None], want_o, 0), atol=2e-5)
    np.testing.assert_allclose(np.where(some, lse, 0), np.where(some, want_lse, 0), atol=2e-5)
    if not some.all():
        # A row that keeps no key at all: the kernel's o is 0 (the XLA form's is the mean of every value), and its
        # log-sum-exp the XLA form's, of nothing but `NEG_INF`.
        none = np.broadcast_to(~some, lse.shape)
        xla_lse = xla_attention(q, k, v, keep=keep, return_lse=True)[1]
        np.testing.assert_array_equal(np.asarray(lse)[none], np.asarray(xla_lse)[none])
        assert (np.asarray(lse)[none] == np.float32(NEG_INF)).all()
        if backend == "pallas":
            assert (np.asarray(o)[np.broadcast_to(~some[..., None], o.shape)] == 0).all()
        return
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


@pytest.mark.parametrize("shape,blocks", [
    (None, {"block_q": 128, "block_k": 128}), ((1, 8, 1, 128, ()), {"block_q": 384, "block_k": 384}),
    ((1, 2, 2, 256, ()), {"block_q": 128, "block_k": 128}), ((2, 2, 2, 64, ()), {"block_q": 384, "block_k": 128}),
    ((1, 8, 4, 64, ()), {})],
    ids=["group2-d64-128", "group8-d128-384", "group1-d256-128", "group1-d64-384x128", "two-groups-of-2-a-program"])
def test_without_a_selection_grouped_heads_stream_pairs_and_equal_heads_run_what_they_ran(shape, blocks):
    from ray_tpu.ops.flash_attention import _streams_pairs

    shape = shape or SELECTED
    q, k, v, _ = _selection(*shape[:4])
    want_o, want_lse, want = _dense_yardstick(*shape[:4], causal=True)
    # `return_lse` sends equal heads to the pair-streamed kernels too, as a head above 2 MiB goes by itself.
    attn = lambda q, k, v: flash_attention(q, k, v, backend="pallas", interpret=True, return_lse=True, **blocks)

    def loss(q, k, v):
        o, lse = attn(q, k, v)
        return (o ** 2).sum(), (o, lse)

    (_, (o, lse)), got = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse), atol=2e-5)
    xla_o, xla_lse = xla_attention(q, k, v, return_lse=True)
    np.testing.assert_allclose(np.asarray(xla_o), np.asarray(o), atol=2e-5)
    np.testing.assert_allclose(np.asarray(xla_lse), np.asarray(lse), atol=2e-5)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)
    assert _streams_pairs(384, 64, 4, kv_heads_fewer=True, keep=False)
    assert not _streams_pairs(4096, 256, 2, False, False) and _streams_pairs(4096 + 512, 256, 2, False, False)
    assert not _streams_pairs(8192, 128, 2, False, False) and _streams_pairs(16384, 128, 2, False, False)
    # The plan of a call that streams pairs: never unrolled, 512 x 1024 tiles; the others' are what they were.
    assert kernel_plan((1, 32, 16384, 128)) == (512, 1024, 272, 32, 512, False)
    assert kernel_plan((1, 4, 1024, 64), keep=True) == (512, 1024, 2, 2, 2, False)
    assert kernel_plan((1, 4, 1024, 64), kv_heads=2) == (512, 1024, 2, 2, 2, False)
    assert kernel_plan((1, 8, 8192, 256), keep=True) == (512, 512, 136, 16, 256, False)  # wider than the lanes: 512-tiles
    assert kernel_plan((1, 4, 1024, 64), kv_heads=4) == kernel_plan((1, 4, 1024, 64)) == (512, 512, 3, 2, 4, True)


def test_the_forward_takes_a_group_or_several_a_program_by_what_it_holds():
    """`_fwd_pairs_plan`: the query heads a program of the pair-streamed forward takes and its Q tile, from the
    shapes alone; the backward's plan is not touched (`kernel_plan` above)."""
    from ray_tpu.ops.flash_attention import FWD_PAIRS_VMEM_BYTES, _fwd_pairs_bytes, _fwd_pairs_plan

    def taken(heads, kv_heads, seq, d, keep, itemsize=2):
        plan = kernel_plan((1, heads, seq, d), kv_heads=kv_heads, keep=keep)
        return _fwd_pairs_plan(heads // kv_heads, heads, d, itemsize, plan)

    assert taken(32, 4, 16384, 128, True) == (8, 512)  # the Keye cell: a key/value head's whole group, 11.8 MiB
    assert _fwd_pairs_bytes(8, 1, 512, 1024, 128, 2) == 11.8125 * 2 ** 20 <= FWD_PAIRS_VMEM_BYTES
    assert taken(32, 32, 16384, 128, False) == (4, 512)  # equal heads: four of them, each with its own k and v
    assert taken(8, 8, 8192, 256, False) == (4, 512)
    assert taken(32, 1, 2048, 128, False) == (32, 128)  # one key/value head under 32: all of them, a quarter of the Q tile
    assert taken(32, 8, 2048, 128, False) == (8, 512)  # llama's 32 on 8: two groups of four with their two key/value heads
    assert taken(12, 4, 1536, 64, True) == (12, 512)  # 512-tiles cut to the row; three groups of three
    assert taken(2, 1, 384, 64, True, itemsize=4) == (2, 384)
    for heads, kv_heads, seq, d in ((32, 4, 16384, 128), (32, 32, 16384, 128), (8, 8, 8192, 256), (32, 1, 2048, 128)):
        took, tile_q = taken(heads, kv_heads, seq, d, True)
        plan = kernel_plan((1, heads, seq, d), kv_heads=kv_heads, keep=True)
        assert heads % took == 0 and plan.tile_q % tile_q == 0 and tile_q % 128 == 0
        assert _fwd_pairs_bytes(took, max(took * kv_heads // heads, 1), tile_q, plan.tile_k, d, 2) <= FWD_PAIRS_VMEM_BYTES


def test_the_forward_schedule_visits_each_pair_once_a_q_tile_at_a_time():
    from ray_tpu.ops.flash_attention import KernelPlan, _fwd_schedule

    steps = _fwd_schedule(2048, KernelPlan(256, 512, 0, 0, 0, False), True)
    i, j, first, masked, last = steps[:5]  # rows 5-6: the pair's live span of keys (`tests/test_flash_mask.py`)
    assert steps.shape == (7, sum(-(-(n + 1) * 256 // 512) for n in range(8)))
    assert len({(a, b) for a, b in zip(i, j)}) == steps.shape[1] and (np.diff(i) >= 0).all()
    for tile in range(8):
        mine = i == tile
        assert list(j[mine]) == list(range(mine.sum())) and first[mine][0] == 1 and last[mine][-1] == 1
        assert first[mine].sum() == last[mine].sum() == 1 and masked[mine][-1] == 1
    full = _fwd_schedule(1024, KernelPlan(512, 512, 0, 0, 0, False), False)
    assert full.shape == (7, 4) and not full[3].any() and (full[5:] == [[0], [4]]).all()



def test_keep_at_the_pair_forms_own_tiles_of_512_by_1024():
    """No tile sizes asked for: a Q tile of 512 on a K tile of 1,024, eight bits of a word a pair, the
    diagonal crossing each K tile's two Q tiles at another offset."""
    from ray_tpu.ops.flash_attention import pack_keep

    keys = jax.random.split(jax.random.PRNGKey(17), 4)
    q = jax.random.normal(keys[0], (1, 2, 2048, 64), jnp.float32)
    k, v = (jax.random.normal(kk, (1, 1, 2048, 64), jnp.float32) for kk in keys[1:3])
    mask = (jax.random.bernoulli(keys[3], 0.1, (1, 2048, 2048)) | jnp.eye(2048, dtype=bool)) & jnp.tril(
        jnp.ones((2048, 2048), bool))
    assert kernel_plan(q.shape, kv_heads=1, keep=True, dtype=jnp.float32)[:2] == (512, 1024)
    attn = lambda q, k, v: flash_attention(q, k, v, keep=pack_keep(mask), backend="pallas", interpret=True)
    both = lambda f: jax.jit(jax.value_and_grad(lambda *a: (lambda o: ((o ** 2).sum(), o))(f(*a)), argnums=(0, 1, 2),
                                                has_aux=True))
    (_, o), got = both(attn)(q, k, v)
    (_, want_o), want = both(lambda *a: _dense_masked(*a, mask)[0])(q, k, v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), atol=2e-5)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


# (Q tile, K tile) at which the causal diagonal leaves a crossed pair a half, or one to three quarters, of its K tile
# to score (PR 48): both kernels walk that span alone. With and without a selection, 4 heads on 2 and on 4, and a head
# of 128 at the plan's K tile.
@pytest.mark.parametrize("seq,d,tiles,heads,kv_heads,selected", [
    (512, 32, (128, 256), 4, 2, False), (512, 32, (128, 256), 4, 2, True), (512, 32, (128, 256), 4, 4, False),
    (512, 32, (128, 256), 4, 4, True), (2048, 64, (256, 1024), 2, 1, True), (2048, 128, (256, 1024), 2, 2, False)],
    ids=["group2", "group2-keep", "equal", "equal-keep", "256x1024-group2-keep", "256x1024-equal-d128"])
def test_a_pair_scored_over_its_live_span_alone_gives_what_xla_gives(seq, d, tiles, heads, kv_heads, selected):
    import importlib

    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    plan = fa._kernel_blocks(seq, d, True, *tiles, 4, True)
    assert fa._short_spans(fa._pair_schedule(seq, plan, True), tiles[1]) == tuple(range(tiles[0], tiles[1], tiles[0]))
    keys = jax.random.split(jax.random.PRNGKey(seq + d), 5)
    q, w = (jax.random.normal(kk, (1, heads, seq, d), jnp.float32) for kk in keys[:2])
    k, v = (jax.random.normal(kk, (1, kv_heads, seq, d), jnp.float32) for kk in keys[2:4])
    keep = fa.pack_keep((jax.random.bernoulli(keys[4], 0.2, (1, seq, seq)) | jnp.eye(seq, dtype=bool))
                        & jnp.tril(jnp.ones((seq, seq), bool))) if selected else None

    def both(attn):
        def loss(q, k, v):
            o, lse = attn(q, k, v, keep=keep, return_lse=True)
            return (o * w).sum(), (o, lse)
        (_, (o, lse)), grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return o, lse, grads

    o, lse, got = both(lambda *a, **kw: flash_attention(*a, backend="pallas", interpret=True, block_q=tiles[0],
                                                        block_k=tiles[1], **kw))
    want_o, want_lse, want = both(xla_attention)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse), atol=2e-5)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


# ------------------------------------------------------------------ what the kernels keep for their backward pass (PR 60)
# (batch, heads, key/value heads, a selection, return_lse, fsdp)
@pytest.mark.parametrize("batch,heads,kv_heads,selected,return_lse,fsdp", [
    (1, 4, 2, False, False, None), (2, 4, 2, False, False, None), (2, 4, 2, True, True, None),
    (1, 2, 2, True, False, None), (2, 2, 2, False, True, None), (2, 4, 1, True, True, 2), (2, 2, 2, False, True, 2)],
    ids=["b1-group2", "b2-group2", "b2-group2-keep-lse", "b1-equal-keep", "b2-equal-lse", "b2-group4-keep-lse-fsdp2",
         "b2-equal-lse-fsdp2"])
def test_the_pair_forms_give_bit_for_bit_what_they_gave_with_the_reshapes_outside(batch, heads, kv_heads, selected,
                                                                                  return_lse, fsdp):
    """`_flash_pairs` takes and returns the caller's (batch, heads, seq, d), k and v with their own head count,
    and flattens inside its rules: o, lse and the three gradients (dk and dv after the sum over a group) are
    what the boundary before gave, to the bit, in every form the entry takes and in a `shard_map` a row a device."""
    from ray_tpu.ops.flash_attention import pack_keep
    from ray_tpu.parallel import MeshSpec

    q, k, v, mask = _selection(batch, heads, kv_heads, 64)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    do = jax.random.normal(jax.random.PRNGKey(60), q.shape, jnp.float32).astype(jnp.bfloat16)
    mesh = MeshSpec(fsdp=fsdp).build(jax.devices()[:fsdp]) if fsdp else None
    call = dict(keep=pack_keep(mask) if selected else None, return_lse=return_lse, mesh=mesh, block_q=128, block_k=128)
    (now, grads), (before, grads_before) = flash_residuals.both_boundaries(q, k, v, do, **call)
    assert len(now) == 1 + return_lse and (not return_lse or now[1].shape == q.shape[:3])
    for got, want in zip(now + list(grads), before + list(grads_before)):
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    assert [g.shape for g in grads] == [q.shape, k.shape, v.shape] and np.abs(np.asarray(grads[1], np.float32)).max() > 0


@pytest.mark.parametrize("kv_heads,return_lse,of_os_size", [(2, False, 2), (4, True, 4)], ids=["group2", "equal-lse"])
def test_a_layer_scan_stacks_the_pair_forms_output_once(kv_heads, return_lse, of_os_size):
    """Two layers of `stack.block` under "save_attn" on grouped heads, the Keye and SDAR cells' form: beside q
    the forward scan of `jax.grad` stacks exactly one array of o's size, and both in the caller's shape. With
    the reshapes outside the `custom_vjp` it stacked two: the rule's (batch * heads, seq, d) and the (batch,
    heads, seq, d) that `out_part`'s checkpoint saves, 640 MiB at the cells' five layers of 32 x 16,384 x 128."""
    shape = (2, 4, 256, 64)

    def attention(q, k, v):
        out = flash_attention(q, k, v, backend="pallas", interpret=True, return_lse=return_lse)
        return out[0] if return_lse else out

    stacked = flash_residuals.stacked_by_the_forward_scan(attention, shape, kv_heads)
    assert [s for s in stacked if s[-2:] == shape[-2:] and s[2] == shape[1]] == [(2, *shape)] * of_os_size, stacked
    assert len([s for s in stacked if s[-2:] == shape[-2:]]) == 4  # q, k, v, o: nothing of a head's size beside them
