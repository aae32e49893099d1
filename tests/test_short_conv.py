"""`ops/short_conv.py`: the chain of XLA operations against the arithmetic `models/olmo_hybrid.py` made until PR 54
(`_conv_silu`, to heads-first, the L2 norm, q's scale, the cast), values and the gradients to z and to every tap in
float32, a row's first three positions (the zeros before it) against a convolution written out; the gradient
kernel `short_conv_bwd` in interpret mode against jax's gradient of the chain, at widths that fill lane rows (heads
of 96 and of 192), at a last tile that passes the array's end, at the nano model's widths, under a mesh; and which
form a call takes."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from ray_tpu.ops import short_conv as sc  # noqa: E402

# (heads, channels a head, normalised): channels a multiple of 384 (q or k: four heads a tile; v: two); a last tile
# half outside the array (the cell's q: 2,880 channels are 7.5 tiles); the nano model's 48 + 48 + 96.
WIDTHS = {"q_768": (8, 96, True), "v_768": (4, 192, False), "q_576": (6, 96, True),
          "nano_q": (4, 12, True), "nano_v": (4, 24, False)}
SEQ = 48


def operands(name, dtype, batch=2, seq=SEQ, seed=0):
    heads, d, _ = WIDTHS[name]
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    z = jax.random.normal(keys[0], (batch, seq, heads * d)).astype(dtype)
    taps = jax.random.normal(keys[1], (4, heads * d)) * 0.5
    weights = jax.random.normal(keys[2], (batch, heads, seq, d))
    return z, taps, weights


def old_arithmetic(z, taps, heads, scale, normalize):
    """`models/olmo_hybrid.py linear_qkv`'s lines at PR 53, for one of q, k, v."""
    def _shifted(z, n):
        return z if n == 0 else jnp.pad(z, ((0, 0), (n, 0), (0, 0)))[:, :z.shape[1]]

    n = taps.shape[0]
    zf, w = z.astype(jnp.float32), taps.astype(jnp.float32)
    y = jax.nn.silu(sum(w[j] * _shifted(zf, n - 1 - j) for j in range(n)))
    b, s, _ = y.shape
    y = y.reshape(b, s, heads, -1).transpose(0, 2, 1, 3)
    if normalize:
        y = y * jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6)
    if scale != 1.0:
        y = y * scale
    return y.astype(z.dtype)


def value_and_grads(f, z, taps, weights):
    loss = lambda z, taps: (f(z, taps).astype(jnp.float32) * weights).sum()  # noqa: E731
    return jax.jit(lambda z, taps: (f(z, taps), jax.grad(loss, argnums=(0, 1))(z, taps)))(z, taps)


def far(a, b):
    """The largest distance over the reference's largest value."""
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.abs(a - b).max() / jnp.abs(b).max())


def call(name, **how):
    heads, d, normalize = WIDTHS[name]
    scale = d ** -0.5 if normalize else 1.0
    return lambda z, taps: sc.short_conv(z, taps, heads, scale=scale, normalize=normalize, **how)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("name", list(WIDTHS))
def test_the_chain_is_the_arithmetic_the_model_made(name, dtype):
    heads, d, normalize = WIDTHS[name]
    z, taps, weights = operands(name, dtype)
    old = lambda z, taps: old_arithmetic(z, taps, heads, d ** -0.5 if normalize else 1.0, normalize)  # noqa: E731
    (out, (dz, dtaps)), (want, (want_dz, want_dtaps)) = (
        value_and_grads(f, z, taps, weights) for f in (call(name, backend="xla"), old))
    assert out.shape == (2, heads, SEQ, d) and out.dtype == dtype and dz.dtype == dtype and dtaps.dtype == taps.dtype
    np.testing.assert_array_equal(np.asarray(out, np.float32), np.asarray(want, np.float32))  # the same roundings
    assert far(dz, want_dz) < 1e-6 and far(dtaps, want_dtaps) < 1e-6
    # The row's first positions meet the zeros before it: position t takes taps[3 - t:] on z[:t + 1].
    zf = np.asarray(z, np.float32)
    for t in range(3):
        pre = sum(np.asarray(taps)[3 - k] * zf[:, t - k] for k in range(t + 1))
        y = (pre / (1 + np.exp(-pre))).reshape(2, heads, d)
        if normalize:
            y = y / np.sqrt((y * y).sum(-1, keepdims=True) + 1e-6) * d ** -0.5
        assert far(out[:, :, t], y) < (1e-5 if dtype == jnp.float32 else 1e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("name", list(WIDTHS))
def test_the_kernels_gradient_is_the_chains(name, dtype):
    """Forward both forms are the same XLA operations (to how XLA fuses them beside a different gradient: an f32
    rounding); the kernel's gradient against jax's of the chain: to f32's rounding on f32 operands, to one
    rounding of dz on bf16 ones (the taps' gradient stays f32)."""
    z, taps, weights = operands(name, dtype)
    (out, (dz, dtaps)), (want, (want_dz, want_dtaps)) = (
        value_and_grads(call(name, backend=backend, interpret=True), z, taps, weights) for backend in ("pallas", "xla"))
    assert out.dtype == dtype and far(out, want) < (1e-6 if dtype == jnp.float32 else 2 ** -8)
    assert dz.dtype == dtype and dtaps.dtype == taps.dtype
    assert far(dz, want_dz) < (2e-6 if dtype == jnp.float32 else 2 ** -7)
    assert far(dtaps, want_dtaps) < 2e-6
    # every tap's gradient, and the first positions' (their earlier neighbours are the zeros before the row)
    assert all(far(dtaps[j], want_dtaps[j]) < 5e-6 for j in range(4))
    assert far(dz[:, :3], want_dz[:, :3]) < (5e-6 if dtype == jnp.float32 else 2 ** -6)


@pytest.mark.parametrize("name", ["q_768", "v_768"])
def test_a_row_of_many_steps_carries_the_gradient_across_them(name):
    """640 positions are five steps of 128 of a program's walk from the row's end (twenty of 32 without the norm):
    dpre's first rows go to the step before."""
    z, taps, weights = operands(name, jnp.float32, batch=1, seq=640)
    (_, (dz, dtaps)), (_, (want_dz, want_dtaps)) = (
        value_and_grads(call(name, backend=backend, interpret=True), z, taps, weights) for backend in ("pallas", "xla"))
    assert far(dz, want_dz) < 2e-6 and far(dtaps, want_dtaps) < 2e-6


def test_under_a_mesh_the_taps_gradient_is_the_sum_over_every_devices_rows():
    from ray_tpu.parallel import MeshSpec

    mesh = MeshSpec(fsdp=4).build(jax.devices()[:4])
    z, taps, weights = operands("q_768", jnp.float32, batch=4)
    (_, (dz, dtaps)), (_, (want_dz, want_dtaps)) = (
        value_and_grads(call("q_768", backend="pallas", interpret=True, mesh=m), z, taps, weights) for m in (mesh, None))
    assert far(dz, want_dz) < 1e-6 and far(dtaps, want_dtaps) < 1e-6
    with_tensor = MeshSpec(fsdp=2, tensor=2).build(jax.devices()[:4])  # whole heads over `tensor`
    _, (dz, dtaps) = value_and_grads(call("q_768", backend="pallas", interpret=True, mesh=with_tensor), z, taps, weights)
    assert far(dz, want_dz) < 1e-6 and far(dtaps, want_dtaps) < 1e-6


def test_the_form_is_chosen_by_the_platform_and_the_shapes():
    # The cell's q, k (30 heads of 96: 7.5 tiles) and v (30 of 192) fit; so does a row of 65,536 / 8.
    assert sc.tile_of(96) == sc.tile_of(192) == 384
    assert sc.mosaic_fits((1, 4096, 2880), 96, 4, 2, True) and sc.mosaic_fits((1, 4096, 5760), 192, 4, 2, False)
    assert sc.mosaic_fits((1, 8192, 5760), 192, 4, 2, False)
    assert (sc.rows_a_step(4096, True), sc.rows_a_step(4096, False), sc.rows_a_step(48, True)) == (128, 32, 16)
    # The nano model's widths are narrower than a tile; a row of 49 is no whole number of steps; heads of 80 would
    # make a tile of 640; a row of 65,536 does not fit VMEM whole.
    assert not sc.mosaic_fits((2, 48, 48), 12, 4, 4, True) and not sc.mosaic_fits((1, 49, 768), 96, 4, 4, True)
    assert not sc.mosaic_fits((1, 4096, 2560), 80, 4, 2, True) and not sc.mosaic_fits((1, 65536, 5760), 192, 4, 2, False)
    # On this platform a call is the chain alone, whatever the shapes; asked for, the gradient holds the kernel.
    z, taps, _ = operands("q_768", jnp.bfloat16)
    grad = lambda **how: str(jax.make_jaxpr(jax.grad(  # noqa: E731
        lambda z, taps: call("q_768", **how)(z, taps).astype(jnp.float32).sum(), argnums=(0, 1)))(z, taps))
    assert "pallas_call" not in grad() and "short_conv_bwd" in grad(backend="pallas")
    with pytest.raises(ValueError, match="neither"):
        call("q_768", backend="mosaic")(z, taps)
    with pytest.raises(ValueError, match="16 positions"):
        call("q_768", backend="pallas")(z[:, :40], taps)


def test_the_bench_tool_walks_every_form_off_the_chip():
    """`tools/short_conv_bench.py --rehearse`: the old chain, the module and the gated norm's chain at a small
    shape in interpret mode, no timing; the module's line carries its distance from the old chain."""
    import json
    import subprocess

    done = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "short_conv_bench.py"), "--rehearse", "--shape", "1x32x4x96x192",
         "--form", "chain,module,xla,out_chain"], capture_output=True, text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    lines = {line["form"]: line for line in map(json.loads, done.stdout.strip().splitlines())}
    assert list(lines) == ["chain", "module", "xla", "out_chain"] and all(line["rehearsal"] for line in lines.values())
    assert max(lines["module"]["check"].values()) < 1e-5 and max(lines["xla"]["check"].values()) == 0.0
