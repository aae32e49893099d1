"""`ops/flash_attention.py`: the whole-head and loop forms of the Pallas kernels (interpret mode on CPU; the
same kernels compile for TPU: `tests/test_aot_v5e.py`), `blockwise_attention`, dropout and `kernel_plan`. The
pair-streamed forms are `tests/test_flash_pairs.py`'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flash_residuals
from attention_yardsticks import TOLERANCE, _kernel_against_xla
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


# ------------------------------------------------------------------ what the kernels keep for their backward pass (PR 60)
@pytest.mark.parametrize("batch,fsdp,blocks", [
    (1, None, {}), (2, None, {}), (2, None, {"block_q": 128, "block_k": 128}), (2, 2, {})],
    ids=["b1", "b2", "b2-loops-128", "b2-fsdp2"])
def test_the_whole_head_forms_give_bit_for_bit_what_they_gave_with_the_reshapes_outside(batch, fsdp, blocks):
    """`_flash_bhsd` takes and returns the caller's (batch, heads, seq, d) and flattens inside its rules: the
    same kernels on the same operands, so o and the three gradients are what the boundary before gave, to the
    bit, for one row and two, unrolled and in loops, and in a `shard_map` that gives each device a row."""
    from ray_tpu.parallel import MeshSpec

    keys = jax.random.split(jax.random.PRNGKey(60 + batch), 4)
    q, k, v, do = (jax.random.normal(kk, (batch, 2, 256, 64), jnp.float32).astype(jnp.bfloat16) for kk in keys)
    mesh = MeshSpec(fsdp=fsdp).build(jax.devices()[:fsdp]) if fsdp else None
    (now, grads), (before, grads_before) = flash_residuals.both_boundaries(q, k, v, do, mesh=mesh, **blocks)
    for got, want in zip(now + list(grads), before + list(grads_before)):
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    assert [g.shape for g in grads] == [q.shape, k.shape, v.shape] and np.abs(np.asarray(grads[0], np.float32)).max() > 0


def test_a_layer_scan_stacks_the_whole_head_forms_output_once():
    """Two layers of `stack.block` under "save_attn": the forward scan of `jax.grad` stacks q, k, v and o, each
    once and in the caller's shape. With the reshapes outside the `custom_vjp` it stacked o twice, the rule's
    (batch * heads, seq, d) and the (batch, heads, seq, d) that `out_part`'s checkpoint saves: five, not four."""
    shape = (2, 4, 256, 64)
    stacked = flash_residuals.stacked_by_the_forward_scan(
        lambda q, k, v: flash_attention(q, k, v, backend="pallas", interpret=True), shape, kv_heads=4)
    heads = [s for s in stacked if s[-2:] == shape[-2:]]
    assert heads == [(2, *shape)] * 4, stacked
