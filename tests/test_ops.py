"""Pallas kernel tests (interpret mode on CPU; the same kernels compile for TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.flash_attention import flash_attention, xla_attention


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
    assert select_backend((1, 8, 8192, 128), platform="tpu") == "blockwise"
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
