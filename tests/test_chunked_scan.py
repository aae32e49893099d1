"""`ops/chunked_scan.py`, the walk the two delta rules share, held to itself over both: a row that is no whole number
of chunks through the Mosaic kernels in interpret mode (the padding, the running sum, the gate's block by the rule's
layout, the reverse walk's gradient of the gate) against the XLA form of the same rule, and the plan's scopes in the
lowered text. What each rule's own mathematics is held to is in `test_gated_delta_rule.py` and `test_kda.py`."""

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops import chunked_scan as walk
from ray_tpu.ops import gated_delta_rule as gdn
from ray_tpu.ops import kda

RULES = {"gdn": (gdn.RULE, gdn.gated_delta_rule), "kda": (kda.RULE, kda.kimi_delta_rule)}
SHAPE = (1, 2, 20, 12, 24)  # 20 positions: two chunks of 8 and half a third; widths that fill no lane row
CHUNK = 8


def operands(rule):
    """q, k, v, g, beta as a linear layer hands them: q and k L2-normalised over a head, q scaled; `g` a number or a
    vector of d_k a position by the rule's layout; `beta` in (0, 2)."""
    b, h, s, dk, dv = SHAPE
    kq, kk, kv, kg, kb = jax.random.split(jax.random.PRNGKey(3), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(kq, (b, h, s, dk))) * dk ** -0.5
    k = unit(jax.random.normal(kk, (b, h, s, dk)))
    v = jax.random.normal(kv, (b, h, s, dv))
    g = -jax.nn.softplus(jax.random.normal(kg, (b, h, s) + ((dk,) if rule.gate_a_channel else ())))
    return q, k, v, g, 2.0 * jax.nn.sigmoid(jax.random.normal(kb, (b, h, s)))


def far(a, b):
    return float(jnp.abs(a - b).max() / jnp.abs(b).max())


@pytest.mark.parametrize("name", sorted(RULES))
def test_a_padded_row_through_the_kernels_is_the_xla_forms(name):
    rule, entry = RULES[name]
    args = operands(rule)
    weights = jax.random.normal(jax.random.PRNGKey(4), (*SHAPE[:3], SHAPE[4]))

    def value_and_grads(backend):
        f = lambda *a: entry(*a, chunk=CHUNK, backend=backend, interpret=True)  # noqa: E731
        return f(*args), jax.grad(lambda *a: (f(*a) * weights).sum(), argnums=(0, 1, 2, 3, 4))(*args)

    (got, grads), (want, want_grads) = value_and_grads("pallas"), value_and_grads("xla")
    assert got.shape == want.shape == (*SHAPE[:3], SHAPE[4])
    assert far(got, want) < 2e-5
    for what, a, b in zip(("q", "k", "v", "g", "beta"), grads, want_grads):
        assert a.shape == b.shape and far(a, b) < 1e-4, what


@pytest.mark.parametrize("name", sorted(RULES))
def test_the_plans_scopes_and_the_kernels_names_are_in_the_lowered_text(name):
    rule, entry = RULES[name]
    q, k, v, g, beta = operands(rule)
    flat = lambda x: x.reshape(-1, *x.shape[2:])[:, :2 * CHUNK]  # noqa: E731  (as the kernels see a padded row's heads)
    heads, chunk_scope, heads_scope = walk.plan(rule, flat(k), flat(v), CHUNK)
    assert (heads, chunk_scope, heads_scope) == (2, "chunk_8", "heads_2of2")
    f = lambda *a: entry(*a, chunk=CHUNK, backend="pallas", interpret=True).sum()  # noqa: E731
    forward = jax.jit(f).lower(q, k, v, g, beta).as_text(debug_info=True)
    assert f"{chunk_scope}/{heads_scope}/{rule.kernels}_fwd" in forward
    # Under a gradient jax writes the transformation round the outermost scope: `transpose(jvp(chunk_8))/heads_2of2`.
    backward = jax.jit(jax.grad(f, argnums=(0, 3))).lower(q, k, v, g, beta).as_text(debug_info=True)
    assert f"{chunk_scope})/{heads_scope}/{rule.kernels}_fwd" in backward
    assert f"{chunk_scope}))/{heads_scope}/{rule.kernels}_bwd" in backward
