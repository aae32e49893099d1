"""`ops/kda.py` (the channel-wise gated delta rule) against the recurrence token by token
(`benchmark/models/solar_open2.py kda_recurrence`, the yardstick of the cell's `check` too): the chunked XLA form,
the Mosaic kernels in interpret mode with their hand-written backward pass, and one chunk's vector-Jacobian product
against jax's own; values and all five gradients in float32, at widths that are no multiple of a lane row, `beta` on
both sides of 1, a row that is no whole number of chunks, decays down to -5 a step (over a chunk `e^gamma` against
`e^-gamma` would overflow); the rule with a decay constant over a head's channels is `gated_delta_rule`; what a
state or a decay kept in bf16 costs."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.models.solar_open2 import kda_recurrence  # noqa: E402
from ray_tpu.ops import chunked_scan as walk  # noqa: E402
from ray_tpu.ops import gated_delta_rule as gdn  # noqa: E402
from ray_tpu.ops import kda  # noqa: E402

SHAPE = (1, 2, 100, 12, 24)  # 100 positions: three chunks of 32 and 4 of a fourth; widths that fill no lane row
STRONG = -5.0  # the log decay of the strongest channels a step: 31 steps of it are e^-155 (e^155 overflows f32)


def operands(shape, seed=0, dtype=jnp.float32, strongest=STRONG):
    """q, k, v, g, beta as a linear layer hands them: q and k L2-normalised over a head, q scaled; a decay a
    channel from near 1 down to `exp(strongest)` a step; `beta` in (0, 2)."""
    batch, heads, seq, dk, dv = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(keys[0], (batch, heads, seq, dk))) * dk ** -0.5
    k = unit(jax.random.normal(keys[1], (batch, heads, seq, dk)))
    v = jax.random.normal(keys[2], (batch, heads, seq, dv))
    # A channel's own rate, from 0.0025 to `-strongest` a step, times 0.5..1 by the position.
    rate = jnp.exp(jax.random.uniform(keys[3], (batch, heads, 1, dk), minval=-6.0, maxval=np.log(-strongest)))
    g = -rate * jax.random.uniform(keys[5], (batch, heads, seq, dk), minval=0.5, maxval=1.0)
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(keys[4], (batch, heads, seq)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def recurrence(q, k, v, g, beta):
    with jax.default_matmul_precision("highest"):
        return jax.vmap(jax.vmap(kda_recurrence))(q, k, v, g, beta)


def value_and_grads(f, args, weights):
    loss = lambda *a: (f(*a).astype(jnp.float32) * weights).sum()  # noqa: E731
    return jax.jit(lambda *a: (f(*a), jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*a)))(*args)


@pytest.fixture(scope="module")
def case():
    args = operands(SHAPE)
    assert float(args[4].min()) < 0.5 and float(args[4].max()) > 1.5  # both sides of 1
    assert float(args[3].min()) < -4.0 and float(args[3].max()) > -0.01
    weights = jax.random.normal(jax.random.PRNGKey(9), (*SHAPE[:3], SHAPE[4]))
    return args, weights, value_and_grads(recurrence, args, weights)


def far(a, b):
    """The largest distance over the reference's largest value."""
    return float(jnp.abs(a.astype(jnp.float32) - b).max() / jnp.abs(b).max())


@pytest.mark.parametrize("backend,chunk", [("xla", 32), ("xla", 8), ("pallas", 32), ("pallas", 16)])
def test_forms_against_the_recurrence(case, backend, chunk):
    args, weights, (want, want_grads) = case
    f = lambda *a: kda.kimi_delta_rule(*a, chunk=chunk, backend=backend, interpret=True)  # noqa: E731
    got, grads = value_and_grads(f, args, weights)
    assert far(got, want) < 2e-5
    for name, a, b in zip(("q", "k", "v", "g", "beta"), grads, want_grads):
        assert far(a, b) < 1e-4, name


def test_a_chunk_of_strong_decays_would_overflow_the_naive_factors(case):
    """The case is what the halving is for: over a chunk of 32 the running sum passes -88 in some channel, where
    `exp(-gamma)` is infinite in f32; the forms above are finite there and right."""
    g = case[0][3]
    gam = walk._running_sum(jnp.pad(g, ((0, 0), (0, 0), (0, 28), (0, 0))), 32)
    assert float(gam.min()) < -100.0 and not bool(jnp.isfinite(jnp.exp(-gam)).all())


def test_no_exponent_above_zero_is_evaluated(monkeypatch):
    """Every `exp` a chunk evaluates, forward and backward, has an argument <= 0."""
    q, k, v, g, beta = (x[0, 0, :32] for x in operands(SHAPE))
    seen = []
    real = jnp.exp
    monkeypatch.setattr(kda.jnp, "exp", lambda x: (seen.append(float(jnp.max(x))), real(x))[1])
    gam = jnp.cumsum(g, axis=0)
    s = jax.random.normal(jax.random.PRNGKey(3), (SHAPE[3], SHAPE[4]))
    with jax.disable_jit():
        kda._chunk_bwd(q, k, v, gam, beta[None], s, jnp.ones((32, SHAPE[4])), jnp.ones_like(s))
    assert len(seen) >= 8 and max(seen) <= 0.0


def test_chunk_vjp_is_jaxs_own():
    """`_chunk_bwd`, written by hand, against `jax.vjp` of `_chunk_fwd`, every output's cotangent set."""
    q, k, v, g, beta = (x[0, 1, :32] for x in operands(SHAPE, seed=4))
    gam, beta = jnp.cumsum(g, axis=0), beta[None]
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    s = jax.random.normal(keys[0], (SHAPE[3], SHAPE[4]))
    do, ds_new = jax.random.normal(keys[1], (32, SHAPE[4])), jax.random.normal(keys[2], s.shape)
    want = jax.vjp(kda._chunk_fwd, q, k, v, gam, beta, s)[1]((do, ds_new))
    got = jax.jit(kda._chunk_bwd)(q, k, v, gam, beta, s, do, ds_new)
    for name, a, b in zip(("q", "k", "v", "gam", "beta", "s"), got, want):
        assert far(a, b) < 2e-5, name


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_a_decay_constant_over_the_channels_is_the_scalar_rule(backend):
    """With every channel of a head at one decay the rule is `gated_delta_rule`'s, on the same inputs."""
    q, k, v, g, beta = operands(SHAPE, seed=2, strongest=-2.0)
    g = g[..., 0]
    wide = jnp.broadcast_to(g[..., None], (*g.shape, SHAPE[3]))
    weights = jax.random.normal(jax.random.PRNGKey(1), v.shape)
    f = lambda q, k, v, g, beta: kda.kimi_delta_rule(  # noqa: E731
        q, k, v, jnp.broadcast_to(g[..., None], wide.shape), beta, chunk=32, backend=backend, interpret=True)
    scalar = lambda *a: gdn.gated_delta_rule(*a, chunk=32, backend="xla")  # noqa: E731
    got, grads = value_and_grads(f, (q, k, v, g, beta), weights)
    want, want_grads = value_and_grads(scalar, (q, k, v, g, beta), weights)
    assert far(got, want) < 2e-5
    for name, a, b in zip(("q", "k", "v", "g", "beta"), grads, want_grads):
        assert far(a, b) < 1e-4, name


def test_bf16_operands_and_two_heads_a_program():
    """q, k, v in bf16 (a bf16 model's), eight flat heads: two a program by `heads_per_program`, the kernels'
    scope says so; against the XLA form on the same bf16 operands."""
    shape = (2, 4, 64, 16, 16)
    args = operands(shape, seed=7, dtype=jnp.bfloat16, strongest=-1.0)
    weights = jax.random.normal(jax.random.PRNGKey(2), (*shape[:3], shape[4]))
    f = lambda backend: lambda *a: kda.kimi_delta_rule(*a, chunk=32, backend=backend, interpret=True)  # noqa: E731
    got, grads = value_and_grads(f("pallas"), args, weights)
    want, want_grads = value_and_grads(f("xla"), args, weights)
    assert got.dtype == jnp.bfloat16 and far(got, want.astype(jnp.float32)) < 1e-2
    for name, a, b in zip(("q", "k", "v", "g", "beta"), grads, want_grads):
        assert far(a, b.astype(jnp.float32)) < 2e-2, name
    flat = [x.reshape(8, *x.shape[2:]) for x in args[:3]]
    assert walk.plan(kda.RULE, flat[1], flat[2], 32)[1:] == ("chunk_32", "heads_2of8")
    text = jax.jit(f("pallas")).lower(*args).as_text(debug_info=True)
    assert "kda_fwd" in text and "chunk_32/heads_2of8" in text


@pytest.mark.parametrize("what", ["state", "decay"])
def test_a_bf16_state_or_decay_is_told(case, what, monkeypatch):
    """The state a chunk hands on, or the running sum of the decay, rounded to bf16: forty times further from
    the recurrence than the form itself (`test_forms_against_the_recurrence`'s limit)."""
    args, weights, (want, _) = case
    rounded = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    if what == "state":
        real = kda._chunk_fwd
        monkeypatch.setattr(kda, "_chunk_fwd", lambda *a, **kw: (lambda o, s: (o, rounded(s)))(*real(*a, **kw)))
    else:
        real = walk._running_sum
        monkeypatch.setattr(walk, "_running_sum", lambda g, chunk: rounded(real(g, chunk)))
    got = kda.kimi_delta_rule(*args, chunk=32, backend="xla")
    assert far(got, want) > 40 * 2e-5


def test_arguments_are_checked():
    args = operands((1, 1, 16, 8, 8))
    with pytest.raises(ValueError, match="power of two"):
        kda.kimi_delta_rule(*args, chunk=24)
    with pytest.raises(ValueError, match="neither"):
        kda.kimi_delta_rule(*args, backend="triton")


def test_mxu_passes_by_hand():
    """At 128 x 128 x 128: the doubling's 12 products and 7 levels of (a 0/1 product at 3 passes, K K^T and Q K^T
    at 6), then K S, T R, Q S, K^T N, P N at 6; backward four more products a level and ten more against the state
    and the chunk."""
    assert kda.mxu_passes(128, 128, 128) == 72 + 7 * 15 + 12 + 18
    assert kda.mxu_passes(128, 128, 128, backward=True) == 72 + 7 * 15 + 12 + 7 * 24 + 60
    assert kda.chunk_flops(128, 128, 128) == 2 * 128 ** 3 * 207
