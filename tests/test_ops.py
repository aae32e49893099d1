"""Pallas kernel tests (interpret mode on CPU; the same kernels compile for TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.flash_attention import flash_attention, kernel_plan, xla_attention


@pytest.fixture(scope="module")
def qkv():
    key = jax.random.PRNGKey(0)
    b, h, s, d = 2, 2, 256, 64
    return tuple(
        jax.random.normal(k, (b, h, s, d), jnp.float32) for k in jax.random.split(key, 3)
    )


def test_flash_forward_matches_reference(qkv):
    q, k, v = qkv
    ref = xla_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, backend="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_forward_noncausal(qkv):
    q, k, v = qkv
    ref = xla_attention(q, k, v, causal=False)
    out = flash_attention(q, k, v, causal=False, backend="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_backward_matches_reference(qkv):
    q, k, v = qkv

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True, backend="pallas", interpret=True) ** 2).sum()

    def f_ref(q, k, v):
        return (xla_attention(q, k, v, causal=True) ** 2).sum()

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def _kernel_against_xla(seq, head_dim, causal, dtype, **blocks):
    """Output and all three gradients of the kernel (interpret mode) against
    `xla_attention`, as the largest error over the largest reference value."""
    keys = jax.random.split(jax.random.PRNGKey(seq + head_dim), 4)
    q, k, v, do = (jax.random.normal(kk, (1, 2, seq, head_dim), jnp.float32).astype(dtype)
                   for kk in keys)

    def grads_of(attn):
        def f(q, k, v):
            o = attn(q, k, v)
            return (o.astype(jnp.float32) * do.astype(jnp.float32)).sum(), o
        return jax.jit(jax.grad(f, argnums=(0, 1, 2), has_aux=True))

    (dq, dk, dv), o = grads_of(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, backend="pallas", interpret=True, **blocks))(q, k, v)
    (rq, rk, rv), ro = grads_of(lambda q, k, v: xla_attention(q, k, v, causal=causal))(q, k, v)
    errs = {}
    for name, got, ref in (("o", o, ro), ("dq", dq, rq), ("dk", dk, rk), ("dv", dv, rv)):
        got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
        assert np.isfinite(got).all(), name
        errs[name] = float(np.abs(got - ref).max() / np.abs(ref).max())
    return errs


# f32: summation order only. bf16: two roundings of values up to the largest,
# the bound chip_smoke.py holds the chip to.
TOLERANCE = {jnp.float32: 1e-5, jnp.bfloat16: 2.0 ** -6}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("seq", [512, 1024, 1536, 2048])
def test_kernel_matches_xla_over_many_tiles(seq, head_dim, causal, dtype):
    """The schedule the shape picks: from 1024 on several tiles, some skipped,
    some masked, some not (seq 256, the other tests' size, is a single tile)."""
    errs = _kernel_against_xla(seq, head_dim, causal, dtype)
    assert max(errs.values()) <= TOLERANCE[dtype], errs


@pytest.mark.parametrize("seq,causal,block_q,block_k,unrolled", [
    (2048, True, 128, 128, False),  # 136 of 256 tiles: too many to unroll, the loop form
    (1024, False, 128, 128, False),  # 64 of 64, loop form, nothing masked
    (1024, True, 256, 512, True),  # tiles that are not square: 6 of 8, 4 masked
    (1024, True, 512, 128, True),
    (2048, True, 256, 128, False),  # not square in the loop form: 72 of 128
    (1024, True, 1024, 1024, False),  # one masked tile a head: tiles above 512 keep the loop form
    (2048, True, 1024, 512, False),  # 6 of 8, a Q tile a program, two K tiles masked in each
])
def test_kernel_matches_xla_in_both_forms_of_the_schedule(seq, causal, block_q, block_k, unrolled):
    plan = kernel_plan((1, 2, seq, 64), causal, block_q, block_k, dtype=jnp.float32)
    assert (plan.tile_q, plan.tile_k, plan.unrolled) == (block_q, block_k, unrolled)
    errs = _kernel_against_xla(seq, 64, causal, jnp.float32, block_q=block_q, block_k=block_k)
    assert max(errs.values()) <= TOLERANCE[jnp.float32], (plan, errs)


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
        i, j, due, first, masked, whole = fa._pair_schedule(seq, plan, causal)
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


def test_kernel_plan_counts_the_triangle():
    # A square causal schedule of n x n tiles visits n(n+1)/2 and masks n.
    for seq, tile in ((1024, 256), (1024, 128), (2048, 256), (512, 512), (4096, 256)):
        n = seq // tile
        plan = kernel_plan((2, 4, seq, 64), True, tile, tile)
        assert (plan.tiles_visited, plan.tiles_masked, plan.tiles_total) == (n * (n + 1) // 2, n, n * n)
        full = kernel_plan((2, 4, seq, 64), False, tile, tile)
        assert (full.tiles_visited, full.tiles_masked, full.tiles_total) == (n * n, 0, n * n)
    # Not square: tile (i, j) is visited when its first column is not past the
    # tile's last row, masked when its last column is past the first row.
    plan = kernel_plan((1, 1, 1024, 64), True, 256, 512)
    assert (plan.tiles_visited, plan.tiles_masked, plan.tiles_total) == (6, 4, 8)
    # The plan PERF.md records for both benchmark configurations (8 rows x 16
    # heads, 4 rows x 25 heads a chip): 512-tiles, 3 of 4, unrolled.
    for shape in ((8, 16, 1024, 64), (4, 25, 1024, 64)):
        plan = kernel_plan(shape, True)
        assert plan == (512, 512, 3, 2, 4, True) and plan.scope == "tiles_3of4"
    # What the shape decides (ops/flash_attention.py cites the sweep): a
    # triangle too long to unroll, a head too large to hold whole in VMEM, and
    # every non-causal call, walk the largest tile in the loop form as before.
    assert kernel_plan((1, 32, 2048, 128), True) == kernel_plan((1, 8, 2048, 64), True)
    assert kernel_plan((1, 8, 2048, 64), True) == (512, 512, 10, 4, 16, True)
    # 64 lanes pad to 128 in VMEM: a 4096 x 64 head is as long as a 4096 x 128 one (PR 35).
    assert kernel_plan((1, 8, 4096, 64), True) == kernel_plan((1, 8, 4096, 128), True)
    assert kernel_plan((1, 8, 4096, 64), True) == (512, 512, 36, 8, 64, False)
    assert kernel_plan((1, 8, 2048, 128), True, dtype=jnp.float32) == (1024, 1024, 3, 2, 4, False)
    assert kernel_plan((1, 8, 2048, 128), True, 1024, 1024) == (1024, 1024, 3, 2, 4, False)
    assert kernel_plan((8, 16, 1024, 64), False) == (1024, 1024, 1, 0, 1, False)
    assert kernel_plan((1, 1, 1536, 64), True) == (512, 512, 6, 3, 9, True)


def test_misaligned_seq_selection_is_visible_not_silent():
    """A seq len with no block of >=128 dividing it (e.g. 100) cannot run the
    kernel: asked for by name that is an error, and the automatic choice says
    "xla" through select_backend instead of switching silently. Seq lens
    divisible by 512 but not by the 1024 default shrink the block via gcd and
    stay on pallas."""
    from ray_tpu.ops.flash_attention import select_backend

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 2, 100, 64)), jnp.float32)
    with pytest.raises(ValueError, match="no block of at least 128"):
        flash_attention(q, q, q, backend="pallas", interpret=True, block_q=64, block_k=64)
    assert select_backend(q.shape, platform="tpu") == "xla"
    assert select_backend((16, 12, 1024, 64), platform="tpu") == "pallas"
    # By the VMEM a head takes, lanes padded (PR 39): up to 4096 x 256 in bf16, which is
    # 8192 x 128 and 8192 x 64 too, the forward program holds K and V; beyond that it streams
    # them a (Q tile, K tile) pair a program (PR 42) up to the head whose f32 dq the backward
    # program holds (16,384 x 128), and the scan over K blocks is for what no form holds.
    assert select_backend((1, 8, 8192, 128), platform="tpu") == "pallas"
    assert select_backend((2, 20, 4096, 256), platform="tpu") == "pallas"
    assert select_backend((2, 32, 8192, 64), platform="tpu") == "pallas"
    assert select_backend((1, 8, 8192 + 512, 128), platform="tpu") == "pallas"
    assert select_backend((1, 8, 8192 + 512, 64), platform="tpu") == "pallas"
    assert select_backend((1, 8, 4096 + 512, 256), platform="tpu") == "pallas"
    assert select_backend((1, 32, 16384, 128), platform="tpu") == "pallas"
    assert select_backend((1, 8, 16384 + 512, 128), platform="tpu") == "blockwise"
    assert select_backend((1, 8, 16384 + 512, 64), platform="tpu") == "blockwise"
    assert select_backend((1, 8, 8192 + 512, 256), platform="tpu") == "blockwise"
    assert select_backend((16, 12, 1024, 64), platform="cpu") == "xla"
    out = flash_attention(q, q, q)  # this process is on CPU: the XLA form
    ref = xla_attention(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    q2 = jnp.asarray(rng.standard_normal((1, 1, 1536, 64)), jnp.float32)
    assert select_backend(q2.shape, platform="tpu") == "pallas"
    out2 = flash_attention(q2, q2, q2, backend="pallas", interpret=True)  # gcd -> 512
    ref2 = xla_attention(q2, q2, q2, causal=True)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref2), atol=2e-4)


def test_bf16_inputs(qkv):
    q, k, v = (x.astype(jnp.bfloat16) for x in qkv)
    ref = xla_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, backend="pallas", interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2
    )


def test_blockwise_attention_matches_reference(qkv):
    from ray_tpu.ops.flash_attention import blockwise_attention

    q, k, v = qkv
    ref = xla_attention(q, k, v, causal=True)
    out = blockwise_attention(q, k, v, causal=True, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    # gradients flow (remat'ed scan)
    g = jax.grad(lambda q: (blockwise_attention(q, k, v, block_k=64) ** 2).sum())(q)
    g_ref = jax.grad(lambda q: (xla_attention(q, k, v) ** 2).sum())(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=5e-4)


def test_dropout_applied_and_deterministic_eval():
    from ray_tpu.models import GPTConfig, init_params, forward

    cfg = GPTConfig(
        vocab_size=256, max_seq_len=128, n_layer=2, n_head=2, d_model=64,
        dtype=jnp.float32, dropout=0.5, attention="xla",
    )
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.zeros((2, 16), jnp.int32)
    eval1 = forward(params, toks, cfg)                       # no rng -> no dropout
    eval2 = forward(params, toks, cfg)
    np.testing.assert_array_equal(np.asarray(eval1), np.asarray(eval2))
    tr1 = forward(params, toks, cfg, dropout_rng=jax.random.PRNGKey(1))
    tr2 = forward(params, toks, cfg, dropout_rng=jax.random.PRNGKey(2))
    assert np.abs(np.asarray(tr1) - np.asarray(tr2)).max() > 1e-6  # stochastic
    assert np.abs(np.asarray(tr1) - np.asarray(eval1)).max() > 1e-6


# ----------------------------------------------------------------------------- a selection of keys (PR 42)
def _dense_masked(q, k, v, mask):
    """Softmax attention over the keys `mask` (batch, queries, keys) keeps, key/value heads repeated:
    the yardstick, written with no function of `ops/flash_attention.py`."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    s = jnp.where(mask[:, None], s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v), jax.scipy.special.logsumexp(s, axis=-1)


def _selection(b, h, hk, d, s=384, none_at=()):
    """q on `hk` key/value heads, and a random selection under the diagonal in which some query keeps
    no key of its first tile, every query keeps itself, and the queries `none_at` keep no key at all."""
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    q = jax.random.normal(keys[0], (b, h, s, d), jnp.float32)
    k, v = (jax.random.normal(kk, (b, hk, s, d), jnp.float32) for kk in keys[1:3])
    mask = jax.random.bernoulli(keys[3], 0.2, (b, s, s)) | jnp.eye(s, dtype=bool)
    mask = mask.at[:, 200:, :128].set(False) & jnp.tril(jnp.ones((s, s), bool))
    for row in none_at:
        mask = mask.at[:, row].set(False)
    return q, k, v, mask


@pytest.fixture(scope="module")
def selected():
    return _selection(2, 4, 2, 64)


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
def test_keep_forward_and_backward_against_a_dense_masked_softmax(selected, backend, blocks, shape):
    from ray_tpu.ops.flash_attention import NEG_INF, pack_keep

    q, k, v, mask = selected if shape is None else _selection(*shape[:4], none_at=shape[4])
    keep = pack_keep(mask)
    attn = lambda q, k, v: flash_attention(q, k, v, keep=keep, return_lse=True, backend=backend,
                                           interpret=True, **blocks)
    o, lse = attn(q, k, v)
    want_o, want_lse = _dense_masked(q, k, v, mask)
    some = np.asarray(mask.any(axis=-1))[:, None]  # (batch, 1, queries): the rows that keep a key
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
        return  # the gradient of such a row is not defined: the softmax of nothing
    loss = lambda f: lambda q, k, v: (f(q, k, v)[0] ** 2).sum()
    got = jax.grad(loss(attn), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: _dense_masked(q, k, v, mask)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


@pytest.mark.parametrize("shape,blocks", [
    (None, {"block_q": 128, "block_k": 128}), ((1, 8, 1, 128, ()), {"block_q": 384, "block_k": 384}),
    ((1, 2, 2, 256, ()), {"block_q": 128, "block_k": 128}), ((2, 2, 2, 64, ()), {"block_q": 384, "block_k": 128}),
    ((1, 8, 4, 64, ()), {})],
    ids=["group2-d64-128", "group8-d128-384", "group1-d256-128", "group1-d64-384x128", "two-groups-of-2-a-program"])
def test_without_a_selection_grouped_heads_stream_pairs_and_equal_heads_run_what_they_ran(selected, shape, blocks):
    from ray_tpu.ops.flash_attention import _streams_pairs

    q, k, v, _ = selected if shape is None else _selection(*shape[:4])
    causal = jnp.tril(jnp.ones((q.shape[2],) * 2, bool))[None]
    # `return_lse` sends equal heads to the pair-streamed kernels too, as a head above 2 MiB goes by itself.
    attn = lambda q, k, v: flash_attention(q, k, v, backend="pallas", interpret=True, return_lse=True, **blocks)
    o, lse = attn(q, k, v)
    want_o, want_lse = _dense_masked(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse), atol=2e-5)
    xla_o, xla_lse = xla_attention(q, k, v, return_lse=True)
    np.testing.assert_allclose(np.asarray(xla_o), np.asarray(o), atol=2e-5)
    np.testing.assert_allclose(np.asarray(xla_lse), np.asarray(lse), atol=2e-5)
    loss = lambda f: lambda q, k, v: (f(q, k, v)[0] ** 2).sum()
    got = jax.grad(loss(attn), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: _dense_masked(q, k, v, causal)), argnums=(0, 1, 2))(q, k, v)
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
    i, j, first, masked, last = steps
    assert steps.shape == (5, sum(-(-(n + 1) * 256 // 512) for n in range(8)))
    assert len({(a, b) for a, b in zip(i, j)}) == steps.shape[1] and (np.diff(i) >= 0).all()
    for tile in range(8):
        mine = i == tile
        assert list(j[mine]) == list(range(mine.sum())) and first[mine][0] == 1 and last[mine][-1] == 1
        assert first[mine].sum() == last[mine].sum() == 1 and masked[mine][-1] == 1
    full = _fwd_schedule(1024, KernelPlan(512, 512, 0, 0, 0, False), False)
    assert full.shape == (5, 4) and not full[3].any()


def _scores(q_i, k_i, w):
    s = jnp.einsum("bjqd,bkd->bjqk", q_i, k_i)
    return jnp.einsum("bjqk,bqj->bqk", jax.nn.relu(s), w)


def _top_k_set(scores, k):
    """The selection by `lax.top_k`: the keys of the past at or above the k-th largest."""
    seq = scores.shape[-1]
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    masked = jnp.where(causal, scores, -jnp.inf)
    tau = jax.lax.top_k(masked, min(k, seq))[0][..., -1:]
    return (masked >= tau) & causal


def _indexer_inputs(b, heads, seq, d, seed=3):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q_i = jax.random.normal(keys[0], (b, heads, seq, d), jnp.float32)
    k_i = jax.random.normal(keys[1], (b, seq, d), jnp.float32)
    # Keys 100-139 alike: their scores tie for every query, in and out of the top k.
    k_i = k_i.at[:, 100:140].set(k_i[:, 100:101])
    w = jax.random.normal(keys[2], (b, seq, heads), jnp.float32) * 0.3
    return q_i, k_i, w


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


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("case", INDEX_LOSS_CASES)
def test_the_index_loss_and_its_gradient_against_the_dense_form(case, backend, monkeypatch):
    import importlib

    from ray_tpu.ops.flash_attention import pack_keep
    from ray_tpu.ops.lightning_indexer import index_loss, select, selection_counts

    b, heads, kv_heads, seq, d, index_heads, index_d, topk, dtype, tiles = INDEX_LOSS_CASES[case]
    if tiles is not None:
        li = importlib.import_module("ray_tpu.ops.lightning_indexer")
        monkeypatch.setattr(li, "_loss_plan", lambda *shape: li.LossPlan(*tiles[:2], (*tiles, True)[2], 0))
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

    mine = lambda q_i, k_i, w: index_loss(q.astype(dtype), k.astype(dtype), lse, keep, q_i.astype(dtype), k_i.astype(dtype),
                                          w, lse_i, backend=backend, interpret=True)
    tol = 2e-5 if dtype == jnp.float32 else TOLERANCE[dtype]
    want, want_g = jax.value_and_grad(dense, argnums=(0, 1, 2))(q_i, k_i, w)
    got, got_g = jax.value_and_grad(mine, argnums=(0, 1, 2))(q_i, k_i, w)
    assert float(got) == pytest.approx(float(want), rel=tol) and float(want) > 0.05
    for a, b_ in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=tol * float(jnp.abs(b_).max()) + 1e-9)
    # No gradient reaches the attention's own operands, and a cotangent scales the three.
    dq, dk = jax.grad(lambda q, k: index_loss(q, k, lse, keep, q_i, k_i, w, lse_i, backend=backend,
                                              interpret=True), argnums=(0, 1))(q, k)
    assert float(jnp.abs(dq).max()) == float(jnp.abs(dk).max()) == 0.0
    twice = jax.grad(lambda w: 2.0 * mine(q_i, k_i, w))(w)
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
    np.testing.assert_allclose(np.asarray(attn(q, k, v)), np.asarray(_dense_masked(q, k, v, mask)[0]), atol=2e-5)
    got = jax.grad(lambda *a: (attn(*a) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (_dense_masked(*a, mask)[0] ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)
