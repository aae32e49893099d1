"""`flash_attention` with values of a width of their own (keys 192, values 128 in the Xing4.0 cell): the whole-head
forward and both backward forms in interpret mode against the XLA form, which the plan of such a call selects; the
pair-a-program forward refuses; a call of one width lowers to what it lowered to."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

fa = importlib.import_module("ray_tpu.ops.flash_attention")  # the package exports the function under the module's name


def operands(heads, seq, d, dv, seed=0):
    kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = lambda width: (1, heads, seq, width)  # noqa: E731
    return (jax.random.normal(kq, shape(d), jnp.bfloat16), jax.random.normal(kk, shape(d), jnp.bfloat16),
            jax.random.normal(kv, shape(dv), jnp.bfloat16), jax.random.normal(kg, shape(dv), jnp.float32))


# (heads, seq, keys, values, tile): 192 / 128 streams its backward pass a pair a program (`_streamed_head`: wider than
# the lanes); 64 / 32 at 512 is unrolled, at 1,024 with tiles of 256 the loop form of both whole-head kernels.
@pytest.mark.parametrize("heads,seq,d,dv,block", [(2, 512, 192, 128, None), (2, 512, 64, 32, None), (1, 1024, 64, 128, 256),
                                                  (1, 4096, 64, 32, None)])
def test_two_widths_agree_with_the_xla_form(heads, seq, d, dv, block):
    q, k, v, g = operands(heads, seq, d, dv)
    scale = 0.1447 if d == 192 else None

    def of(backend):
        def loss(q, k, v):
            o = fa.flash_attention(q, k, v, causal=True, sm_scale=scale, backend=backend, interpret=True,
                                   block_q=block, block_k=block)
            assert o.shape == v.shape and o.dtype == v.dtype
            return (o.astype(jnp.float32) * g).sum()
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    (want, want_grads), (got, got_grads) = of("xla"), of("pallas")
    assert float(got) == pytest.approx(float(want), rel=2e-2, abs=0.5)
    for name, a, b in zip("qkv", got_grads, want_grads):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a.astype(jnp.float32), b.astype(jnp.float32), rtol=0.05,
                                   atol=0.03 * float(jnp.abs(b.astype(jnp.float32)).max()), err_msg=name)


def test_the_cells_call_is_planned_by_the_keys_width():
    plan = fa.kernel_plan((1, 32, 4096, 192))
    assert (plan.tile_q, plan.tile_k, plan.tiles_visited, plan.tiles_total, plan.unrolled) == (512, 512, 36, 64, False)
    assert fa.select_backend((1, 32, 4096, 192), "tpu") == "pallas"
    assert fa._streamed_head(4096, 192, 2) and not fa._streams_pairs(4096, 192, 2, False, False)


def test_the_pair_a_program_forward_refuses_two_widths():
    q, k, v, _ = operands(4, 512, 64, 32)
    with pytest.raises(NotImplementedError, match="one width"):
        fa.flash_attention(q, k[:, :2], v[:, :2], backend="pallas", interpret=True)  # grouped heads
    with pytest.raises(NotImplementedError, match="one width"):
        fa.flash_attention(q, k, v, causal=fa.SlidingWindow(128), backend="pallas", interpret=True)
