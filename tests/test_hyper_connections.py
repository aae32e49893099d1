"""`ops/hyper_connections.py` against the equations written per token in plain `jax.numpy` (a token's maps as n and
n x n arrays, sums as `sum(axis)`), values and gradients in float32; what the rounds leave of a doubly stochastic
matrix; one stream; the backward rules in both their forms (`jax.numpy`, the kernels in interpret mode) against
`jax.grad` of those equations."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import hyper_connections as mhc

KW = dict(norm_eps=1e-6, rounds=20, eps=1e-6, clamp=(-30.0, 30.0))


def plain(x, phi, alpha, bias, y_of, *, norm_eps, rounds, eps, clamp):
    """x (B, S, n, d), a token at a time by broadcasting: (X', H_res (B, S, n, n))."""
    n = x.shape[2]
    flat = x.reshape(*x.shape[:2], -1)
    r = 1.0 / jnp.sqrt((flat * flat).mean(-1, keepdims=True) + norm_eps)
    m = r * (flat @ phi.reshape(phi.shape[0], -1).T)
    h_pre = jax.nn.sigmoid(alpha[0] * m[..., :n] + bias[:n])
    h_post = 2 * jax.nn.sigmoid(alpha[1] * m[..., n:2 * n] + bias[n:2 * n])
    h_res = jnp.exp(jnp.clip(alpha[2] * m[..., 2 * n:].reshape(*m.shape[:2], n, n) + bias[2 * n:].reshape(n, n), *clamp))
    for _ in range(rounds):
        h_res = h_res / (h_res.sum(-1, keepdims=True) + eps)
        h_res = h_res / (h_res.sum(-2, keepdims=True) + eps)
    y = y_of(jnp.einsum("bsn,bsnd->bsd", h_pre, x))
    return h_post[..., None] * y[:, :, None] + jnp.einsum("bsij,bsjd->bsid", h_res, x), h_res


def through_the_op(x, phi, alpha, bias, y_of, how=None, **kw):
    """The same on the op's layout, x (B, n, S, d): (X' (B, n, S, d), H_res (n, n, B, S)). how: `backend` and
    `interpret` of the two passes whose gradient may be a kernel."""
    how = how or {}
    h_pre, h_post, h_res = mhc.maps(x, phi, alpha, bias, **kw, **how)
    return mhc.post_res_mix(x, y_of(mhc.pre_mix(x, h_pre)), h_post, h_res, **how), h_res


def operands(n, d=32, batch=2, seq=8, seed=0, bias_std=1.0):
    kx, kp, kb, kw = jax.random.split(jax.random.PRNGKey(seed), 4)
    columns = mhc.n_maps(n)
    return (jax.random.normal(kx, (batch, seq, n, d)), jax.random.normal(kp, (columns, n, d)) * (n * d) ** -0.5,
            jnp.asarray([0.7, 1.1, 0.9]), jax.random.normal(kb, (columns,)) * bias_std,
            jax.random.normal(kw, (d, d)) * d ** -0.5)


@pytest.mark.parametrize("n", [4, 2])
def test_values_and_gradients_are_the_equations(n):
    x, phi, alpha, bias, w = operands(n)
    sublayer = lambda u: jnp.tanh(u @ w)  # noqa: E731
    weights = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)  # a loss that sees every entry

    def of_plain(x, phi, alpha, bias):
        return (plain(x, phi, alpha, bias, sublayer, **KW)[0] * weights).sum()

    def of_op(x, phi, alpha, bias):
        out, _ = through_the_op(x.transpose(0, 2, 1, 3), phi, alpha, bias, sublayer, **KW)
        return (out.transpose(0, 2, 1, 3) * weights).sum()

    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.value_and_grad(of_plain, argnums=(0, 1, 2, 3))(x, phi, alpha, bias)
        got, got_grads = jax.jit(jax.value_and_grad(of_op, argnums=(0, 1, 2, 3)))(x, phi, alpha, bias)
    assert got == pytest.approx(float(want), rel=1e-5)
    for name, a, b in zip(("x", "phi", "alpha", "bias"), got_grads, want_grads):
        assert float(jnp.abs(b).max()) > 1e-3, name  # every operand reaches the loss
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5 * float(jnp.abs(b).max()), err_msg=name)


RULES = {"jnp": dict(backend="xla"), "kernels": dict(backend="pallas", interpret=True)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("n", [4, 1])
@pytest.mark.parametrize("rule", sorted(RULES))
def test_a_rules_gradients_are_jax_grad_of_the_equations(rule, n, dtype):
    """Every leaf (x, y through the sublayer's weight, Phi, alpha, the biases) under either form of the rules, three
    tiles of 128 tokens a row, against float32 autodiff of the per-token equations on the same (rounded) streams. A bf16
    sublayer output and bf16 cotangents of the streams round each once: 2^-8 a term."""
    x, phi, alpha, bias, w = operands(n, d=128, seq=384, batch=1, seed=n)
    x = x.astype(dtype)
    assert mhc.token_tile(x.shape[1]) == 128 and mhc.fits(x.transpose(0, 2, 1, 3).shape, x.dtype.itemsize)
    weights = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)

    def of_plain(x, phi, alpha, bias, w):
        sublayer = lambda u: jnp.tanh(u @ w).astype(dtype).astype(jnp.float32)  # noqa: E731
        return (plain(x.astype(jnp.float32), phi, alpha, bias, sublayer, **KW)[0] * weights).sum()

    def of_op(x, phi, alpha, bias, w):
        sublayer = lambda u: jnp.tanh(u @ w).astype(dtype)  # noqa: E731
        out, _ = through_the_op(x.transpose(0, 2, 1, 3), phi, alpha, bias, sublayer, RULES[rule], **KW)
        return (out.transpose(0, 2, 1, 3).astype(jnp.float32) * weights).sum()

    with jax.default_matmul_precision("highest"):
        want = jax.grad(of_plain, argnums=(0, 1, 2, 3, 4))(x, phi, alpha, bias, w)
        got = jax.jit(jax.grad(of_op, argnums=(0, 1, 2, 3, 4)))(x, phi, alpha, bias, w)
    rounding = 2e-4 if dtype == jnp.float32 else 2e-2
    for name, a, b in zip(("x", "phi", "alpha", "bias", "w"), got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert a.dtype == b.dtype and np.abs(b).max() > 1e-3, name
        np.testing.assert_allclose(a, b, rtol=rounding, atol=rounding * np.abs(b).max(), err_msg=name)


def test_both_forms_of_a_rule_round_a_bf16_cotangent_alike():
    """The kernels and the `jax.numpy` rules make the same sums in float32 and round them once: a bf16 stream's
    gradients differ by no more than float32's own rounding of the sums."""
    x, phi, alpha, bias, w = operands(4, d=128, seq=256, seed=9)
    x = x.transpose(0, 2, 1, 3).astype(jnp.bfloat16)
    sublayer = lambda u: jnp.tanh(u @ w).astype(jnp.bfloat16)  # noqa: E731

    def loss(how):
        return lambda x, phi: (through_the_op(x, phi, alpha, bias, sublayer, how, **KW)[0].astype(jnp.float32) ** 2).sum()

    (dx_k, dphi_k), (dx_j, dphi_j) = (jax.jit(jax.grad(loss(RULES[r]), argnums=(0, 1)))(x, phi) for r in ("kernels", "jnp"))
    assert dx_k.dtype == jnp.bfloat16
    np.testing.assert_allclose(dphi_k, dphi_j, rtol=1e-4, atol=1e-5 * float(jnp.abs(dphi_j).max()))
    off = np.abs(np.asarray(dx_k, np.float32) - np.asarray(dx_j, np.float32))
    assert (off > 0).mean() < 0.02 and off.max() <= 2 ** -7 * float(jnp.abs(dx_j.astype(jnp.float32)).max())


def test_three_bf16_products_of_a_bf16_stream_are_the_product_at_highest_precision():
    x, phi, _, _, _ = operands(4, d=256, seq=32, seed=7)
    x = x.transpose(0, 2, 1, 3).astype(jnp.bfloat16)
    phi = phi * jnp.exp(jax.random.normal(jax.random.PRNGKey(1), phi.shape) * 3)  # entries over many binades
    parts = mhc._bf16_parts(phi)
    assert all(p.dtype == jnp.bfloat16 for p in parts)
    np.testing.assert_array_equal(sum(p.astype(jnp.float32) for p in parts[::-1]), phi)  # nothing of Phi is lost
    got = jax.jit(mhc.product)(x, phi)
    want = np.einsum("bnsd,cnd->cbs", np.asarray(x, np.float64), np.asarray(phi, np.float64))
    highest = jnp.einsum("bnsd,cnd->cbs", x.astype(jnp.float32), phi, precision=jax.lax.Precision.HIGHEST)
    scale = np.abs(want).max()
    # float32 rounding of a sum of 1,024 products: as near the float64 product as `HIGHEST`'s own sum is
    assert np.abs(got - want).max() < 4e-7 * scale and np.abs(got - highest).max() < 1e-6 * scale
    one_pass = jnp.einsum("bnsd,cnd->cbs", x, parts[0], preferred_element_type=jnp.float32)
    assert np.abs(one_pass - want).max() > 1e-4 * scale  # what a single bf16 product would lose
    assert mhc.product(x.astype(jnp.float32), phi).dtype == jnp.float32


@pytest.mark.parametrize("shape, why", [((2, 4, 48, 96), "d is no whole lane row"), ((2, 4, 40, 128), "no whole bf16 tile of tokens"),
                                        ((2, 4, 192, 128), "a tile of 64 tokens is no whole lane row of them"),
                                        ((1, 12, 48, 128), "a token's scalars pass one lane row")])
def test_streams_the_kernels_do_not_fit_take_the_jnp_rule(shape, why, monkeypatch):
    from ray_tpu.ops import chunked_scan

    assert not mhc.fits(shape, 2), why
    x = jnp.ones(shape, jnp.bfloat16)
    monkeypatch.setattr(mhc, "select_backend", lambda platform=None: "pallas")  # as on a TPU
    assert chunked_scan.select_backend("tpu") == "pallas" and not mhc._kernels("maps", x, None, None)
    assert mhc._kernels("maps", jnp.ones((2, 4, 256, 128), jnp.bfloat16), None, None)
    with pytest.raises(ValueError, match="take no streams"):
        mhc._kernels("maps", x, "pallas", None)
    with pytest.raises(ValueError, match="neither"):
        mhc._kernels("maps", x, "mosaic", None)
    if shape[1] == 4:  # the call itself differentiates with no kernel in it
        phi = jnp.ones((24, 4, shape[3])) * 0.01
        text = jax.jit(jax.grad(lambda x: mhc.maps(x, phi, jnp.ones(3), jnp.zeros(24), **KW)[0].sum())).lower(x).as_text()
        assert "mhc_pre_bwd" not in text


def test_streams_over_several_devices_take_the_jnp_rule(monkeypatch):
    from jax.sharding import Mesh

    monkeypatch.setattr(mhc, "select_backend", lambda platform=None: "pallas")
    x = jnp.ones((2, 4, 256, 128), jnp.bfloat16)
    devices = np.asarray(jax.devices())
    assert mhc._kernels("post_res_mix", x, None, Mesh(devices[:1], ("data",)))
    if devices.size > 1:
        assert not mhc._kernels("post_res_mix", x, None, Mesh(devices[:2], ("data",)))


def test_h_res_is_doubly_stochastic_to_the_cells_limit_and_the_layouts_agree():
    from benchmark.models import xing4 as bench

    x, phi, alpha, bias, _ = operands(4, seq=64, seed=3)
    with jax.default_matmul_precision("highest"):
        _, _, h_res = mhc.maps(x.transpose(0, 2, 1, 3), phi, alpha, bias, **KW)
        _, want = plain(x, phi, alpha, bias, lambda u: u, **KW)
    np.testing.assert_allclose(h_res.transpose(2, 3, 0, 1), want, rtol=1e-4, atol=1e-6)
    rows, columns = h_res.sum(axis=1), h_res.sum(axis=0)  # axis 1 runs along a row
    assert float(jnp.abs(columns - 1).max()) < 1e-5  # the last normalisation is the columns'
    assert 0 < float(jnp.abs(rows - 1).max()) < bench.RES_SUM_ERR_TOL
    assert float(h_res.min()) > 0
    one_round = mhc.maps(x.transpose(0, 2, 1, 3), phi, alpha, bias, **{**KW, "rounds": 1})[2]
    assert float(jnp.abs(one_round.sum(axis=1) - 1).max()) > bench.RES_SUM_ERR_TOL  # 19 rounds left out are told


def test_the_clamp_holds_where_a_logit_passes_thirty():
    x, phi, alpha, bias, _ = operands(4, seed=5)
    far = bias.at[8].set(100.0).at[13].set(-100.0)  # B_res[0, 0] and B_res[1, 1]: exp(100) is no float32
    _, _, clamped = mhc.maps(x.transpose(0, 2, 1, 3), phi, alpha, far, **KW)
    _, _, free = mhc.maps(x.transpose(0, 2, 1, 3), phi, alpha, far, **{**KW, "clamp": (-1e9, 1e9)})
    assert bool(jnp.isfinite(clamped).all()) and not bool(jnp.isfinite(free).all())
    np.testing.assert_allclose(clamped.sum(axis=0), 1.0, atol=1e-5)


def test_one_stream_keeps_h_res_at_one_whatever_the_maps_hold():
    for seed in range(3):
        x, phi, alpha, bias, _ = operands(1, seed=seed, bias_std=5.0)
        _, _, h_res = mhc.maps(x.transpose(0, 2, 1, 3), phi * 10, alpha, bias, **KW)
        assert h_res.shape == (1, 1, 2, 8)
        np.testing.assert_allclose(h_res, 1.0, atol=1e-5)


def test_the_streams_dtype_goes_through_and_the_maps_are_float32():
    x, phi, alpha, bias, _ = operands(4)
    xb = x.transpose(0, 2, 1, 3).astype(jnp.bfloat16)
    h_pre, h_post, h_res = mhc.maps(xb, phi, alpha, bias, **KW)
    assert {h.dtype for h in (h_pre, h_post, h_res)} == {jnp.dtype(jnp.float32)}
    u = mhc.pre_mix(xb, h_pre)
    assert u.dtype == jnp.float32 and u.shape == (2, 8, 32)
    out = mhc.post_res_mix(xb, u.astype(jnp.bfloat16), h_post, h_res)
    assert out.dtype == jnp.bfloat16 and out.shape == xb.shape
    assert mhc.n_maps(4) == 24
