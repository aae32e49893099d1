"""`ops/gated_delta_rule.py` against the recurrence token by token (`benchmark/models/olmo_hybrid.py
delta_rule_recurrence`, the yardstick of the cell's `check` too): the chunked XLA form, the Mosaic kernels in
interpret mode with their hand-written backward pass, and one chunk's vector-Jacobian product against jax's own;
values and all five gradients, at the published widths 96 / 192 (not padded to a lane row), `beta` on both sides
of 1, a row that is no whole number of chunks; under a mesh; and what a state or a decay kept in bf16 costs."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.models.olmo_hybrid import delta_rule_recurrence  # noqa: E402
from ray_tpu.ops import gated_delta_rule as gdn  # noqa: E402

NAMES = ("q", "k", "v", "g", "beta")
SHAPE = (1, 2, 200, 96, 192)  # 200 positions: three chunks of 64 and 8 of a fourth


def operands(shape, seed=0, dtype=jnp.float32):
    """q, k, v, g, beta as a linear layer hands them: q and k L2-normalised over a head, q scaled; decays from
    0.02 to near 1; `beta` in (0, 2)."""
    batch, heads, seq, dk, dv = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(keys[0], (batch, heads, seq, dk))) * dk ** -0.5
    k = unit(jax.random.normal(keys[1], (batch, heads, seq, dk)))
    v = jax.random.normal(keys[2], (batch, heads, seq, dv))
    g = -jnp.exp(jax.random.uniform(keys[3], (batch, heads, seq), minval=-6.0, maxval=1.4))
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(keys[4], (batch, heads, seq)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def recurrence(q, k, v, g, beta):
    with jax.default_matmul_precision("highest"):
        return jax.vmap(jax.vmap(delta_rule_recurrence))(q, k, v, g, beta)


def value_and_grads(f, args, weights):
    loss = lambda *a: (f(*a).astype(jnp.float32) * weights).sum()  # noqa: E731
    return jax.jit(lambda *a: (f(*a), jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*a)))(*args)


@pytest.fixture(scope="module")
def case():
    args = operands(SHAPE)
    assert float(args[4].min()) < 0.5 and float(args[4].max()) > 1.5  # both sides of 1
    weights = jax.random.normal(jax.random.PRNGKey(9), (*SHAPE[:3], SHAPE[4]))
    return args, weights, value_and_grads(recurrence, args, weights)


def far(a, b):
    """The largest distance over the reference's largest value."""
    return float(jnp.abs(a.astype(jnp.float32) - b).max() / jnp.abs(b).max())


FORMS = {"xla": dict(backend="xla"), "xla_chunk_16": dict(backend="xla", chunk=16),
         "kernels": dict(backend="pallas", interpret=True, chunk=64)}


@pytest.fixture(scope="module")
def computed(case):
    args, weights, _ = case
    return {name: value_and_grads(lambda *a, kw=kw: gdn.gated_delta_rule(*a, **{"chunk": 64, **kw}), args, weights)
            for name, kw in FORMS.items()}


@pytest.mark.parametrize("what", ("o", *NAMES))
@pytest.mark.parametrize("form", sorted(FORMS))
def test_a_form_agrees_with_the_recurrence_token_by_token(case, computed, form, what):
    """Values and each of the five gradients, in float32: the chunked form is the recurrence rearranged, so
    they agree to rounding (3e-7 to 2e-5 here; a state or a decay in bf16 reads 1e-3 to 1e-2, below)."""
    (_, _, (o_ref, grads_ref)), (o, grads) = case, computed[form]
    if what == "o":
        assert o.shape == o_ref.shape and far(o, o_ref) < 2e-5
    else:
        i = NAMES.index(what)
        assert grads[i].shape == grads_ref[i].shape and far(grads[i], grads_ref[i]) < 1e-4


def test_one_chunks_backward_pass_is_the_transpose_jax_makes_of_its_forward_pass():
    q, k, v, g, beta = (x[0, 0, :32] for x in operands((1, 1, 32, 96, 192), seed=3))
    gam, beta = jnp.cumsum(g)[None], beta[None]
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    s = jax.random.normal(keys[0], (96, 192))
    do, ds_new = jax.random.normal(keys[1], (32, 192)), jax.random.normal(keys[2], (96, 192))
    _, vjp = jax.vjp(gdn._chunk_fwd, q, k, v, gam, beta, s)
    want = vjp((do, ds_new))
    got = jax.jit(gdn._chunk_bwd)(q, k, v, gam, beta, s, do, ds_new)
    for name, a, b in zip(("dq", "dk", "dv", "dgam", "dbeta", "ds"), got, want):
        assert a.shape == b.shape and far(a, b) < 1e-5, name


@pytest.mark.parametrize("n", (8, 64, 128))
def test_the_doubled_inverse_is_the_inverse(n):
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(n), (n, n)) * 0.3, -1)
    product = (jnp.eye(n) + a) @ gdn._unit_lower_inverse(a)
    assert float(jnp.abs(product - jnp.eye(n)).max()) < 1e-4


def test_the_kernels_take_the_operands_in_bf16_and_keep_the_state_in_f32(case):
    """The model's call: q, k, v in bf16, gates in f32. The products of operands are bf16 x bf16 with f32
    accumulation, the outputs are rounded once: a few parts in a thousand."""
    args, weights, (o_ref, grads_ref) = case
    cast = tuple(x.astype(jnp.bfloat16) for x in args[:3]) + args[3:]
    o, grads = value_and_grads(lambda *a: gdn.gated_delta_rule(*a, backend="pallas", interpret=True, chunk=64),
                               cast, weights)
    assert o.dtype == jnp.bfloat16 and [x.dtype for x in grads[:3]] == [jnp.bfloat16] * 3
    assert grads[3].dtype == grads[4].dtype == jnp.float32
    assert far(o, o_ref) < 2e-2 and all(far(a, b) < 3e-2 for a, b in zip(grads, grads_ref))


@pytest.mark.parametrize("fault", ("state_in_bf16", "decay_in_bf16", "decay_dropped"))
def test_a_state_or_a_decay_kept_in_bf16_is_told_by_the_float32_comparison(case, fault, monkeypatch):
    """What the tests above would read if the chunk's mathematics kept its state or its decay in bf16, or
    left the decay out: ten times their limit and more in the values, five times and more in the gradients that
    only the scan's backward pass reaches (dk, dg)."""
    args, weights, (o_ref, grads_ref) = case
    parts, forward = gdn._chunk_parts, gdn._chunk_fwd
    bf16 = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    if fault == "state_in_bf16":  # the state a chunk starts from, as a bf16 scratch would hand it on
        monkeypatch.setattr(gdn, "_chunk_fwd", lambda q, k, v, gam, beta, s: forward(q, k, v, gam, beta, bf16(s)))
    elif fault == "decay_in_bf16":
        monkeypatch.setattr(gdn, "_chunk_parts", lambda q, k, v, gam, beta, s: parts(q, k, v, bf16(gam), beta, s))
    else:
        monkeypatch.setattr(gdn, "_chunk_parts",
                            lambda q, k, v, gam, beta, s: parts(q, k, v, jnp.zeros_like(gam), beta, s))
    o, grads = value_and_grads(lambda *a: gdn.gated_delta_rule(*a, backend="xla", chunk=64), args, weights)
    assert far(o, o_ref) > 10 * 2e-5  # (a bf16 state reads 1.2e-3 here, 60 times the limit)
    assert far(grads[1], grads_ref[1]) > 5 * 1e-4 and far(grads[3], grads_ref[3]) > 5 * 1e-4  # (9.5e-4, 7.4e-4)


def test_under_a_mesh_the_kernels_run_a_shard_a_device_and_agree():
    """`fsdp=4` over the 8-device CPU backend: batch over (data, fsdp) in a shard_map, as `flash_attention(mesh=)`."""
    from ray_tpu.parallel import MeshSpec

    mesh = MeshSpec(fsdp=4).build(jax.devices()[:4])
    args = operands((4, 2, 64, 24, 40), seed=5)
    weights = jax.random.normal(jax.random.PRNGKey(6), (4, 2, 64, 40))
    alone = value_and_grads(lambda *a: gdn.gated_delta_rule(*a, backend="xla", chunk=32), args, weights)
    text = jax.jit(lambda *a: gdn.gated_delta_rule(*a, mesh=mesh, backend="pallas", interpret=True, chunk=32)
                   ).lower(*args).as_text()
    assert "shard_map" in text or "manual" in text
    sharded = value_and_grads(
        lambda *a: gdn.gated_delta_rule(*a, mesh=mesh, backend="pallas", interpret=True, chunk=32), args, weights)
    assert far(sharded[0], alone[0]) < 1e-5
    assert all(far(a, b) < 1e-4 for a, b in zip(sharded[1], alone[1]))


def test_the_backend_follows_the_platform_and_a_chunk_is_a_power_of_two():
    assert gdn.select_backend("tpu") == "pallas" and gdn.select_backend("cpu") == "xla"
    assert gdn.select_backend() == "xla"  # this process runs on the CPU
    args = operands((1, 1, 16, 8, 8))
    with pytest.raises(ValueError, match="power of two"):
        gdn.gated_delta_rule(*args, chunk=48)
    with pytest.raises(ValueError, match="neither"):
        gdn.gated_delta_rule(*args, backend="triton")


def test_what_xla_is_told_of_a_chunk_counts_the_doubling():
    # 2 (log2 C - 1) products of C^3 behind T, beside the products against d_k, d_v and the state.
    assert gdn.chunk_flops(128, 96, 192) > 2 * 6 * 2 * 128 ** 3
    assert gdn.chunk_flops(64, 96, 192, backward=True) > 2 * gdn.chunk_flops(64, 96, 192)
    assert np.isclose(gdn.chunk_flops(64, 96, 192), 2 * 64 * 64 * 96 * 2 + 2 * 5 * 2 * 64 ** 3
                      + 2 * 64 * 96 * 192 * 3 + 2 * 64 * 64 * 192 * 2)
