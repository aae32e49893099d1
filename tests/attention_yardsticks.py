"""What the kernel tests of `ops/flash_attention.py` and `ops/lightning_indexer.py` measure against, written
with no function of the two modules' kernels: `tests/test_flash_attention.py`, `tests/test_flash_pairs.py` and
`tests/test_lightning_indexer.py` share them (one file, `tests/test_ops.py`, until PR 46). A helper, not a test file."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops.flash_attention import flash_attention, xla_attention


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


def _dense_masked(q, k, v, mask):
    """Softmax attention over the keys `mask` (batch, queries, keys) keeps, key/value heads repeated:
    the yardstick, written with no function of `ops/flash_attention.py`."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    s = jnp.where(mask[:, None], s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v), jax.scipy.special.logsumexp(s, axis=-1)


@functools.lru_cache(maxsize=None)
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


@functools.lru_cache(maxsize=None)
def _dense_yardstick(b, h, hk, d, none_at=(), causal=False):
    """`_dense_masked` on `_selection`'s operands, under its selection or (`causal`) under the whole triangle:
    (o, lse, the gradients of `(o ** 2).sum()` for q, k and v), made once for the cases that share the shape."""
    q, k, v, mask = _selection(b, h, hk, d, none_at=none_at)
    if causal:
        mask = jnp.tril(jnp.ones((q.shape[2],) * 2, bool))[None]

    def loss(q, k, v):
        o, lse = _dense_masked(q, k, v, mask)
        return (o ** 2).sum(), (o, lse)

    (_, (o, lse)), grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    return o, lse, grads


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

