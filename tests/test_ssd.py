"""`ops/ssd.py` (the Mamba-2 state-space duality scan) against the recurrence position by position
(`benchmark/models/granite_hybrid.py ssd_recurrence`, the yardstick of the cell's `check` too): the chunked XLA form and
the Mosaic kernels in interpret mode with their hand-written backward pass, values and all six gradients (x, B, C, dt,
`A_log`, `D`) in float32; several heads on one B and C, and two groups; values 64 wide under a state of 128; a row that
is no whole number of chunks; decays strong enough that a chunk's sum passes -88 (`exp(gam_i) exp(-gam_j)` would
overflow); one chunk's vector-Jacobian product against jax's own; what a state, a decay or a dt kept in bf16 costs; and
that the walk's two other rules are served as before (`tests/test_gated_delta_rule.py`, `tests/test_kda.py`)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.models.granite_hybrid import ssd_recurrence  # noqa: E402
from ray_tpu.ops import chunked_scan as walk  # noqa: E402
from ray_tpu.ops import ssd  # noqa: E402

SHAPE = (1, 4, 1, 100, 16, 8)  # batch, heads, groups, positions (three chunks of 32 and 4 of a fourth), N, P
NAMES = ("x", "b", "c", "dt", "a_log", "d")


def operands(shape, seed=0, dtype=jnp.float32, rate=64.0):
    """x, B, C, dt, A_log, D as a mixer hands them: dt from 1e-3 to 0.1 (0.3 at a head's strongest positions), `A`
    from 0.5 up to `rate` a head (64: the largest of the HF port's `arange(1, 65)`): the strongest head's log decay
    is -3.4 a position on average and reaches -19."""
    batch, heads, groups, seq, n, p = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(keys[0], (batch, heads, seq, p))
    b = jax.random.normal(keys[1], (batch, groups, seq, n)) * n ** -0.5
    c = jax.random.normal(keys[2], (batch, groups, seq, n))
    dt = jnp.exp(jax.random.uniform(keys[3], (batch, heads, seq), minval=np.log(1e-3), maxval=np.log(0.3)))
    a_log = jnp.log(jnp.linspace(0.5, rate, heads))
    d = 1.0 + 0.5 * jax.random.normal(keys[5], (heads,))
    return x.astype(dtype), b.astype(dtype), c.astype(dtype), dt, a_log, d


def recurrence(x, b, c, dt, a_log, d):
    """(B, H, S, P) of `ssd_recurrence`, a group's B and C for each of its heads."""
    share = x.shape[1] // b.shape[1]
    b, c = (jnp.repeat(z, share, axis=1) for z in (b, c))
    a_head = jax.vmap(lambda x, b, c, dt, a, d: ssd_recurrence(x, b, c, dt, a, d)[0])
    with jax.default_matmul_precision("highest"):
        return jax.vmap(a_head, in_axes=(0, 0, 0, 0, None, None))(x, b, c, dt, -jnp.exp(a_log), d)


def value_and_grads(f, args, weights):
    loss = lambda *a: (f(*a).astype(jnp.float32) * weights).sum()  # noqa: E731
    return jax.jit(lambda *a: (f(*a), jax.grad(loss, argnums=tuple(range(6)))(*a)))(*args)


def far(a, b):
    """The largest distance over the reference's largest value."""
    return float(jnp.abs(a.astype(jnp.float32) - b).max() / jnp.abs(b).max())


def case_of(shape, seed=0):
    args = operands(shape, seed)
    weights = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    return args, weights, value_and_grads(recurrence, args, weights)


@pytest.fixture(scope="module")
def case():
    return case_of(SHAPE)


@pytest.mark.parametrize("backend,chunk", [("xla", 32), ("pallas", 32), ("pallas", 16)])
def test_forms_against_the_recurrence(case, backend, chunk):
    args, weights, (want, want_grads) = case
    f = lambda *a: ssd.ssd(*a, chunk=chunk, backend=backend, interpret=True)  # noqa: E731
    got, grads = value_and_grads(f, args, weights)
    assert got.dtype == jnp.float32 and far(got, want) < 2e-5
    for name, a, b in zip(NAMES, grads, want_grads):
        assert far(a, b) < 1e-4, name


@pytest.mark.parametrize("shape", [(2, 4, 2, 48, 16, 8), (1, 2, 1, 64, 128, 64)],
                         ids=["two_groups_of_two_heads_two_rows", "values_64_wide_under_a_state_of_128"])
def test_the_kernels_at_other_shapes(shape):
    """Two groups of two heads in two rows (the group a program reads follows its head; a program is one head or
    two); and the cell's own widths: values of 64, half a lane row, under B and C of 128."""
    args, weights, (want, want_grads) = case_of(shape, seed=3)
    got, grads = value_and_grads(lambda *a: ssd.ssd(*a, chunk=16, backend="pallas", interpret=True), args, weights)
    assert far(got, want) < 2e-5
    for name, a, b in zip(NAMES, grads, want_grads):
        assert far(a, b) < 1e-4, name


@pytest.mark.parametrize("most", [1])
def test_a_groups_heads_a_program_each(case, most, monkeypatch):
    """dB and dC are summed over a group's heads inside the reverse walk, whatever the heads a program: four programs
    of one head here, one of four in `test_forms_against_the_recurrence`, two of two a group in the next."""
    monkeypatch.setattr(ssd, "RULE", ssd.RULE._replace(max_heads=most))
    args, weights, (want, want_grads) = case
    v, g = args[0] * args[3][..., None], args[3] * -jnp.exp(args[4])[None, :, None]
    flat = lambda z: z.reshape(-1, *z.shape[2:])  # noqa: E731
    assert walk.plan(ssd.RULE, flat(args[1]), flat(v), 32) == (most, "chunk_32", f"heads_{most}of4", "group_4")
    _, grads = value_and_grads(lambda *a: ssd.ssd(*a, chunk=32, backend="pallas", interpret=True), args, weights)
    for name, a, b in zip(NAMES[1:3], grads[1:3], want_grads[1:3]):
        assert far(a, b) < 1e-4, name


def test_a_chunk_of_strong_decays_would_overflow_the_naive_factors(case):
    """The case is what the masked difference is for: over a chunk of 32 the running sum passes -88 in the strongest
    head, where `exp(-gam)` is no f32 number, and the forms above still agree with the recurrence."""
    x, b, c, dt, a_log, d = case[0]
    gam = walk._running_sum(jnp.pad(dt * -jnp.exp(a_log)[None, :, None], ((0, 0), (0, 0), (0, 28))), 32)
    assert float(gam.min()) < -88.0 and not bool(jnp.isfinite(jnp.exp(-gam)).all())
    assert bool(jnp.isfinite(case[2][0]).all()) and all(bool(jnp.isfinite(g).all()) for g in case[2][1])


def test_no_exponent_above_zero_is_evaluated(monkeypatch):
    """Every `exp` a chunk evaluates, forward and backward, has an argument <= 0."""
    x, b, c, dt, a_log, d = operands(SHAPE)
    seen = []
    real = jnp.exp
    monkeypatch.setattr(ssd.jnp, "exp", lambda x: (seen.append(float(jnp.max(x))), real(x))[1])
    gam = jnp.cumsum(dt[0, 3, :32] * -real(a_log[3]))[None]
    s = jax.random.normal(jax.random.PRNGKey(3), (SHAPE[4], SHAPE[5]))
    with jax.disable_jit():
        ssd._chunk_bwd(c[0, 0, :32], b[0, 0, :32], x[0, 3, :32], gam, None, s, jnp.ones((32, SHAPE[5])), jnp.ones_like(s))
    assert len(seen) >= 4 and max(seen) <= 0.0


def test_chunk_vjp_is_jaxs_own():
    """`_chunk_bwd`, written by hand, against `jax.vjp` of `_chunk_fwd`, every output's cotangent set."""
    x, b, c, dt, a_log, _ = operands(SHAPE, seed=4)
    q, k, v = c[0, 0, :32], b[0, 0, :32], x[0, 2, :32]
    gam = jnp.cumsum(dt[0, 2, :32] * -jnp.exp(a_log[2]))[None]
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    s = jax.random.normal(keys[0], (SHAPE[4], SHAPE[5]))
    do, ds_new = jax.random.normal(keys[1], (32, SHAPE[5])), jax.random.normal(keys[2], s.shape)
    want = jax.vjp(lambda q, k, v, gam, s: ssd._chunk_fwd(q, k, v, gam, None, s), q, k, v, gam, s)[1]((do, ds_new))
    dq, dk, dv, dgam, dbeta, ds = jax.jit(ssd._chunk_bwd)(q, k, v, gam, None, s, do, ds_new)
    assert dbeta is None
    for name, a, b in zip(("q", "k", "v", "gam", "s"), (dq, dk, dv, dgam, ds), want):
        assert far(a, b) < 2e-5, name


def test_bf16_operands_reach_the_products_as_they_come(case):
    """A bf16 model's x, B and C: the result leaves in their type (`D x` is added in f32, the sum rounded once)
    within bf16's rounding of the float32 form's, in both forms."""
    args = operands(SHAPE, dtype=jnp.bfloat16)
    want = ssd.ssd(*(a.astype(jnp.float32) for a in args), chunk=32, backend="xla")
    for backend in ("xla", "pallas"):
        got = ssd.ssd(*args, chunk=32, backend=backend, interpret=True)
        assert got.dtype == jnp.bfloat16 and far(got, want) < 2e-2, backend


@pytest.mark.parametrize("what", ["state", "decay", "dt"])
def test_a_bf16_state_decay_or_dt_is_told(what, monkeypatch):
    """The state a chunk hands on, the running sum of the decay, or dt, rounded to bf16: ten times further from the
    recurrence than the form itself may be (`test_forms_against_the_recurrence`'s limit; the form reads 1e-7), at
    decays under which a state lives long enough to matter (`A` up to 8)."""
    args = operands(SHAPE, rate=8.0)
    want = recurrence(*args)
    rounded = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    if what == "state":
        real = ssd._chunk_fwd
        monkeypatch.setattr(ssd, "_chunk_fwd", lambda *a, **kw: (lambda o, s: (o, rounded(s)))(*real(*a, **kw)))
    elif what == "decay":
        real = walk._running_sum
        monkeypatch.setattr(walk, "_running_sum", lambda g, chunk: rounded(real(g, chunk)))
    else:
        args = (*args[:3], rounded(args[3]), *args[4:])
    assert far(ssd.ssd(*args, chunk=32, backend="xla"), want) > 10 * 2e-5


def test_the_state_after_the_last_position_is_the_recurrences(case):
    """`state_after`, the forward kernel's own hand-over behind a row that is no whole number of chunks."""
    x, b, c, dt, a_log, d = case[0]
    a_head = jax.vmap(lambda x, dt, a, d: ssd_recurrence(x, b[0, 0], c[0, 0], dt, a, d)[1])
    with jax.default_matmul_precision("highest"):
        want = a_head(x[0], dt[0], -jnp.exp(a_log), d)
    got = ssd.state_after(x, b, dt, a_log, chunk=32)
    assert got.shape == (1, 4, 16, 8) and far(got[0], want) < 2e-5


def test_arguments_are_checked():
    x, b, c, dt, a_log, d = operands((1, 4, 1, 16, 8, 8))
    with pytest.raises(ValueError, match="power of two"):
        ssd.ssd(x, b, c, dt, a_log, d, chunk=24)
    with pytest.raises(ValueError, match="neither"):
        ssd.ssd(x, b, c, dt, a_log, d, backend="triton")
    with pytest.raises(ValueError, match="no group's"):
        ssd.ssd(x, jnp.concatenate([b] * 3, axis=1), jnp.concatenate([c] * 3, axis=1), dt, a_log, d)


def test_mxu_passes_and_the_plan_by_hand():
    """At the cell's 128 x 128 x 64 in bf16: C B^T one pass, Q S and K^T V three passes of half a unit, P V three
    passes of half a unit; backward four products against the state and P^T dO at three passes of half a unit, dP K
    and dP^T Q at three whole, dO V^T one half. Four heads a program of the cell's 64 on one B and C."""
    assert ssd.mxu_passes(128, 128, 64, jnp.bfloat16) == 1 + 3 * (2 * 0.5 + 0.5)
    assert ssd.mxu_passes(128, 128, 64, jnp.bfloat16, backward=True) == 1 + 3 * (4 * 0.5 + 0.5 + 2) + 0.5
    assert ssd.mxu_passes(128, 128, 64, jnp.float32) == 6 * (1 + 1.5)
    assert ssd.chunk_flops(128, 128, 64, jnp.bfloat16) == 2 * 128 ** 3 * 5.5
    k, v = jax.ShapeDtypeStruct((1, 4096, 128), jnp.bfloat16), jax.ShapeDtypeStruct((64, 4096, 64), jnp.bfloat16)
    assert walk.plan(ssd.RULE, k, v, ssd.CHUNK) == (ssd.HEADS, f"chunk_{ssd.CHUNK}", f"heads_{ssd.HEADS}of64", "group_64")
    assert not ssd.RULE.inverse and ssd.RULE.kernels == "ssd"
    # the delta rules' plans are what they were: a head a q and k, three a program
    assert walk.heads_per_program(30, 4096, 128, 96, 192, 2) == 3 and walk.heads_per_program(8, 4096, 128, 128, 128, 4) == 2
