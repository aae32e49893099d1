"""`ops/short_conv.py`: the chain of XLA operations against the arithmetic `models/olmo_hybrid.py` made until PR 54
(`_conv_silu`, to heads-first, the L2 norm, q's scale, the cast), values and the gradients to z and to every tap in
float32, a row's first three positions (the zeros before it) against a convolution written out; the gradient
kernel `short_conv_bwd` in interpret mode against jax's gradient of the chain, at widths that fill lane rows (heads
of 96 and of 192), at a last tile that passes the array's end, at the nano model's widths, under a mesh; and which
form a call takes. The gated form (LFM2's, PR 62) likewise: its chain against `models/lfm2.py`'s lines at PR 61 bit
for bit, its kernel `gated_conv_bwd` against jax's gradient of that chain, and which form a call takes."""

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


@pytest.mark.parametrize("heads,seq,dtype", [(6, 48, jnp.float32), (12, 96, jnp.bfloat16)],
                         ids=["x_4_heads_b_c_1_each_of_64_f32", "three_steps_of_32_bf16"])
def test_a_bias_a_channel_before_the_silu_and_its_gradient(heads, seq, dtype):
    """A Mamba-2 mixer's convolution (PR 73): a bias a channel under the SiLU, B's and C's channels further heads of
    64 behind x's, two heads a lane row. The chain against the convolution written out; the kernel's gradient
    (the bias's a further row of the taps' block, a column sum of dpre) against jax's of the chain."""
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    z = jax.random.normal(keys[0], (2, seq, heads * 64)).astype(dtype)
    taps, bias = jax.random.normal(keys[1], (4, heads * 64)) * 0.5, jax.random.normal(keys[2], (heads * 64,))
    weights = jax.random.normal(keys[3], (2, heads, seq, 64))

    def grads(backend):
        f = lambda z, taps, bias: sc.short_conv(z, taps, heads, bias=bias, backend=backend, interpret=True)  # noqa: E731
        loss = lambda *a: (f(*a).astype(jnp.float32) * weights).sum()  # noqa: E731
        return jax.jit(lambda *a: (f(*a), jax.grad(loss, argnums=(0, 1, 2))(*a)))(z, taps, bias)

    (out, (dz, dtaps, dbias)), (want, (want_dz, want_dtaps, want_dbias)) = grads("pallas"), grads("xla")
    zf = jnp.pad(z.astype(jnp.float32), ((0, 0), (3, 0), (0, 0)))
    written_out = jax.nn.silu(sum(taps[j] * zf[:, j:j + seq] for j in range(4)) + bias)
    assert far(want, written_out.reshape(2, seq, heads, 64).transpose(0, 2, 1, 3)) < (1e-6 if dtype == jnp.float32 else 2 ** -8)
    assert out.dtype == dtype and far(out, want) < (1e-6 if dtype == jnp.float32 else 2 ** -8)
    assert dz.dtype == dtype and dtaps.shape == taps.shape and dbias.shape == bias.shape and dbias.dtype == bias.dtype
    assert far(dz, want_dz) < (2e-6 if dtype == jnp.float32 else 2 ** -7)
    assert far(dtaps, want_dtaps) < 2e-6 and far(dbias, want_dbias) < 2e-6
    # without a bias the call is what it was: no further row, no third gradient
    plain = jax.grad(lambda z, taps: sc.short_conv(z, taps, heads, backend="pallas", interpret=True).astype(jnp.float32).sum(),
                     argnums=(0, 1))(z, taps)
    assert len(plain) == 2 and plain[1].shape == taps.shape


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


# The gated form: (D, S, taps). 1,024 channels take the widest tile (512 divides them), 768 one of 384 and 640 one of
# 128 (512 does not divide them); 32 positions are one step of a program's walk, 160 five, 48 three of 16.
GATED = {"d1024_one_step": (1024, 32, 3), "d768_five_steps": (768, 160, 3), "d640_two_taps": (640, 48, 2),
         "d256_two_programs_a_row": (256, 64, 3)}


def old_gated_arithmetic(bcu, taps):
    """`models/lfm2.py short_conv`'s lines under `conv_mix` at PR 61: the shifts are of the product z = b u."""
    d, n = bcu.shape[2] // 3, taps.shape[0]
    b, c, u = (bcu[..., i * d:(i + 1) * d].astype(jnp.float32) for i in range(3))
    z, w = b * u, taps.astype(jnp.float32)
    return (c * sum(w[j] * sc._shifted(z, n - 1 - j) for j in range(n))).astype(bcu.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("name", list(GATED))
def test_the_gated_chain_is_the_models_arithmetic_and_its_kernels_gradient_is_the_chains(name, dtype):
    """Forward the chain shifts `bcu` where the model shifted b u: the same float32 products, bit for bit. The
    kernel's gradient against jax's of the old chain (float32 between bcu and y, as the kernel): to f32's rounding
    on f32 operands, to the one rounding of dbcu on bf16 ones; the taps' gradient stays f32."""
    d, seq, n = GATED[name]
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    bcu = jax.random.normal(keys[0], (3, seq, 3 * d)).astype(dtype)
    taps, weights = jax.random.normal(keys[1], (n, d)) * 0.5, jax.random.normal(keys[2], (3, seq, d))
    assert sc.gated_tile(seq, d, bcu.dtype.itemsize) == {1024: 512, 768: 384, 640: 128, 256: 256}[d]
    (out, (dbcu, dw)), (chain_out, _), (want, (want_dbcu, want_dw)) = (
        value_and_grads(f, bcu, taps, weights) for f in (
            lambda bcu, taps: sc.gated_short_conv(bcu, taps, backend="pallas", interpret=True),
            lambda bcu, taps: sc.gated_short_conv(bcu, taps, backend="xla"), old_gated_arithmetic))
    assert out.shape == (3, seq, d) and out.dtype == dbcu.dtype == dtype and dw.dtype == taps.dtype
    for got in (out, chain_out):
        np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    assert far(dbcu, want_dbcu) < (2e-6 if dtype == jnp.float32 else 2 ** -7)
    assert far(dw, want_dw) < 2e-6 and all(far(dw[j], want_dw[j]) < 5e-6 for j in range(n))
    # each third by itself (db, dc, du are column blocks D apart of one array), and the row's first positions
    for k in range(3):
        third = slice(k * d, (k + 1) * d)
        assert far(dbcu[..., third], want_dbcu[..., third]) < (5e-6 if dtype == jnp.float32 else 2 ** -7)
    assert far(dbcu[:, :2], want_dbcu[:, :2]) < (5e-6 if dtype == jnp.float32 else 2 ** -6)


def test_the_gated_form_is_chosen_by_the_platform_and_the_shapes():
    # The cell's layer fits at the widest tile; a row of 8,192 or 16,384 takes a narrower one; one of 65,536 none.
    assert sc.gated_fits((8, 4096, 6144), 3, 2) and sc.gated_tile(4096, 2048, 2) == sc.GATED_TILE == 512
    assert (sc.gated_tile(8192, 2048, 2), sc.gated_tile(16384, 2048, 2), sc.gated_tile(65536, 2048, 2)) == (256, 128, 0)
    assert not sc.gated_fits((1, 65536, 6144), 3, 2)
    # The nano model's 64 channels are no lane row; 40 positions no whole steps; ten taps pass the halo.
    assert not sc.gated_fits((2, 64, 192), 3, 4) and not sc.gated_fits((2, 40, 768), 3, 4)
    assert not sc.gated_fits((2, 64, 768), 10, 4) and sc.gated_fits((2, 64, 768), 3, 4)
    bcu, taps = jnp.zeros((2, 64, 768), jnp.bfloat16), jnp.ones((3, 256))
    grad = lambda bcu=bcu, **how: str(jax.make_jaxpr(jax.grad(  # noqa: E731
        lambda bcu, taps: sc.gated_short_conv(bcu, taps, **how).astype(jnp.float32).sum(), argnums=(0, 1)))(bcu, taps))
    # On this platform a call is the chain alone; asked for, the gradient holds the kernel and keeps bcu and the taps.
    assert "pallas_call" not in grad() and "gated_conv_bwd" in grad(backend="pallas")
    assert "pallas_call" not in grad(mesh=jax.sharding.Mesh(np.array(jax.devices()[:2]), ("data",)))
    with pytest.raises(ValueError, match="neither"):
        sc.gated_short_conv(bcu, taps, backend="mosaic")
    with pytest.raises(ValueError, match="takes no bcu"):
        sc.gated_short_conv(bcu[:, :40], taps, backend="pallas")


def test_the_bench_tool_walks_every_form_off_the_chip():
    """`tools/short_conv_bench.py --rehearse`: the old chain, the module, the gated norm's chain and LFM2's gated
    form (its old chain, the module) at small shapes in interpret mode, no timing; a module's line carries its
    distance from its old chain."""
    import json
    import subprocess

    done = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "short_conv_bench.py"), "--rehearse", "--shape", "1x32x4x96x192",
         "--gated-shape", "2x64x256x3", "--form", "chain,module,xla,out_chain,gated_chain,gated"],
        capture_output=True, text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    lines = {line["form"]: line for line in map(json.loads, done.stdout.strip().splitlines())}
    assert list(lines) == ["chain", "module", "xla", "out_chain", "gated_chain", "gated"]
    assert all(line["rehearsal"] for line in lines.values())
    assert max(lines["module"]["check"].values()) < 1e-5 and max(lines["xla"]["check"].values()) == 0.0
    assert lines["gated"]["check"]["out"] == 0.0 and max(lines["gated"]["check"].values()) < 1e-5
    assert lines["gated"]["shape"] == "2x64x256x3" and lines["module"]["shape"] == "1x32x4x96x192"
