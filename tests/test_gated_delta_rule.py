"""`ops/gated_delta_rule.py` against the recurrence token by token (`benchmark/models/olmo_hybrid.py
delta_rule_recurrence`, the yardstick of the cell's `check` too): the chunked XLA form, the Mosaic kernels in
interpret mode with their hand-written backward pass, and one chunk's vector-Jacobian product against jax's own;
values and all five gradients, at the published widths 96 / 192 (not padded to a lane row), `beta` on both sides
of 1, a row that is no whole number of chunks, one, two and three heads a program; under a mesh; what a state or a
decay kept in bf16 costs; and the MXU passes a chunk's products are issued at, by their operands' types."""

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
SHAPE = (1, 3, 200, 96, 192)  # 200 positions: three chunks of 64 and 8 of a fourth; three heads, one program's at most


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


# A form: the call's options, and the heads it is handed (the first so many of the case's: heads share nothing). The
# kernels walk every head they are handed in one program here (`heads_per_program`): 1, 2 and 3 heads a program.
KERNELS = dict(backend="pallas", interpret=True, chunk=64)
FORMS = {"xla": (dict(backend="xla"), 3), "xla_chunk_16": (dict(backend="xla", chunk=16), 3),
         "kernels": (KERNELS, 1), "kernels_2_heads": (KERNELS, 2), "kernels_3_heads": (KERNELS, 3)}


def first_heads(tree, heads):
    return jax.tree.map(lambda x: x[:, :heads], tree)


@pytest.fixture(scope="module")
def computed(case):
    args, weights, _ = case
    return {name: value_and_grads(lambda *a, kw=kw: gdn.gated_delta_rule(*a, **{"chunk": 64, **kw}),
                                  first_heads(args, heads), first_heads(weights, heads))
            for name, (kw, heads) in FORMS.items()}


@pytest.mark.parametrize("what", ("o", *NAMES))
@pytest.mark.parametrize("form", sorted(FORMS))
def test_a_form_agrees_with_the_recurrence_token_by_token(case, computed, form, what):
    """Values and each of the five gradients, in float32: the chunked form is the recurrence rearranged, so
    they agree to rounding (3e-7 to 2e-5 here; a state or a decay in bf16 reads 1e-3 to 1e-2, below)."""
    (o_ref, grads_ref), (o, grads) = first_heads(case[2], FORMS[form][1]), computed[form]
    if what == "o":
        assert o.shape == o_ref.shape and far(o, o_ref) < 2e-5
    else:
        i = NAMES.index(what)
        assert grads[i].shape == grads_ref[i].shape and far(grads[i], grads_ref[i]) < 1e-4


def one_chunk(dtype, seed=3, n=32):
    """A chunk's operands as a kernel's program holds them: q, k, v and do in `dtype`, the rest f32."""
    q, k, v, g, beta = (x[0, 0, :n] for x in operands((1, 1, n, 96, 192), seed=seed, dtype=dtype))
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    s, ds_new = jax.random.normal(keys[0], (96, 192)), jax.random.normal(keys[2], (96, 192))
    return (q, k, v, jnp.cumsum(g)[None], beta[None], s), (jax.random.normal(keys[1], (n, 192)).astype(dtype), ds_new)


@pytest.mark.parametrize("dtype, limit", ((jnp.float32, 1e-5), (jnp.bfloat16, 1e-5)), ids=("float32", "bfloat16"))
def test_one_chunks_backward_pass_is_the_transpose_jax_makes_of_its_forward_pass(dtype, limit):
    """In bf16 operands too, where the row scalings stand on the other side of a product than in the forward
    pass's lines: jax's transpose of `_chunk_fwd` is taken at the operands' f32 values there, which is what the
    three-pass products compute."""
    args, (do, ds_new) = one_chunk(dtype)
    _, vjp = jax.vjp(gdn._chunk_fwd, *(x.astype(jnp.float32) for x in args))
    want = vjp((do.astype(jnp.float32), ds_new))
    got = jax.jit(gdn._chunk_bwd)(*args, do, ds_new)
    for name, a, b in zip(("dq", "dk", "dv", "dgam", "dbeta", "ds"), got, want):
        assert a.shape == b.shape and far(a, b) < limit, name


@pytest.mark.parametrize("n", (8, 64, 128))
def test_the_doubled_inverse_is_the_inverse(n):
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(n), (n, n)) * 0.3, -1)
    product = (jnp.eye(n) + a) @ gdn._unit_lower_inverse(a)
    assert float(jnp.abs(product - jnp.eye(n)).max()) < 1e-4


@pytest.mark.parametrize("heads", (1, 2, 3))
def test_the_kernels_take_the_operands_in_bf16_and_keep_the_state_in_f32(case, heads):
    """The model's call: q, k, v in bf16, gates in f32, one to three heads a program. The products of two operands
    are bf16 x bf16 with f32 accumulation, those of an operand and an f32 array three passes, the outputs are
    rounded once: a few parts in a thousand."""
    args, weights, (o_ref, grads_ref) = first_heads(case, heads)
    assert gdn.heads_per_program(heads, 256, 64, 96, 192, 2) == heads
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
    gates, forward = gdn._chunk_gates, gdn._chunk_fwd
    bf16 = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    if fault == "state_in_bf16":  # the state a chunk starts from, as a bf16 scratch would hand it on
        monkeypatch.setattr(gdn, "_chunk_fwd", lambda q, k, v, gam, beta, s: forward(q, k, v, gam, beta, bf16(s)))
    elif fault == "decay_in_bf16":
        monkeypatch.setattr(gdn, "_chunk_gates", lambda k, gam, beta: gates(k, bf16(gam), beta))
    else:
        monkeypatch.setattr(gdn, "_chunk_gates", lambda k, gam, beta: gates(k, jnp.zeros_like(gam), beta))
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


def test_what_xla_is_told_of_a_chunk_counts_the_doubling_and_every_pass_that_is_issued():
    """2 (log2 C - 1) products of C^3 behind T at six passes each, beside the products against d_k, d_v and the
    state: a product of two f32 arrays six times, one of a bf16 operand and an f32 array three times, one of two
    bf16 operands once (`mxu_passes`, in units of 128^3)."""
    unit = 128 ** 3
    kk, ks, cv = 64 * 64 * 96 / unit, 64 * 96 * 192 / unit, 64 * 64 * 192 / unit
    doubling = 6 * 2 * 5 * 64 ** 3 / unit
    bf16, f32 = jnp.bfloat16, jnp.float32
    assert np.isclose(gdn.mxu_passes(64, 96, 192, bf16), doubling + 2 * kk + 3 * 3 * ks + 6 * 2 * cv)
    assert np.isclose(gdn.mxu_passes(64, 96, 192, f32), doubling + 6 * (2 * kk + 3 * ks + 2 * cv))
    assert np.isclose(gdn.mxu_passes(64, 96, 192, bf16, backward=True),
                      doubling + 2 * kk + 3 * (5 * ks + 2 * cv + 4 * kk) + 6 * (2 * ks + 3 * cv))
    assert np.isclose(gdn.mxu_passes(64, 96, 192, f32, backward=True), doubling + 6 * (6 * kk + 7 * ks + 5 * cv))
    # At the cell's chunk: 72 passes of doubling a kernel; beside them 29.625 forward and 76.875 backward, where
    # six passes for every product with an f32 operand were 39.75 and 111.75 (PR 51's kernels).
    assert np.isclose(gdn.mxu_passes(128, 96, 192, bf16), 72 + 29.625)
    assert np.isclose(gdn.mxu_passes(128, 96, 192, bf16, backward=True), 72 + 76.875)
    assert gdn.chunk_flops(128, 96, 192, bf16) == 2 * unit * (72 + 29.625) > 2 * 6 * 2 * 6 * 128 ** 3
    assert gdn.chunk_flops(64, 96, 192, f32, backward=True) == round(
        2 * unit * gdn.mxu_passes(64, 96, 192, f32, backward=True))


def products(f, *args):
    """[(whether at full f32 precision, the operands' types, its multiply-adds)] of every product in `f`'s jaxpr."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                exact = eqn.params["precision"] is not None and all(
                    p == jax.lax.Precision.HIGHEST for p in eqn.params["precision"])
                ((ca,), (cb,)), _ = eqn.params["dimension_numbers"]
                a, b = (v.aval.shape for v in eqn.invars)
                found.append((exact, tuple(v.aval.dtype.name for v in eqn.invars), a[1 - ca] * a[ca] * b[1 - cb]))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(f)(*args).jaxpr)
    return found


@pytest.mark.parametrize("dtype", (jnp.bfloat16, jnp.float32), ids=("bfloat16", "float32"))
@pytest.mark.parametrize("backward", (False, True), ids=("forward", "backward"))
def test_a_product_is_issued_at_six_passes_exactly_where_both_operands_are_f32(dtype, backward):
    """In the traced chunk, the doubling's 12 products (chunk 128) and 2 more forward (`T R`, `P N`), 5 backward
    (`T R`, `N dS'^T`, `T^T dN`, `dR N^T`, `dKS S^T`) with bf16 operands; with f32 operands every one, 12 + 7 and
    12 + 18 (`K K^T` and `Q K^T` among them). Every other product has two bf16 operands: an f32 array never meets
    the MXU at less than six passes, only as its three bf16 parts against a bf16 operand."""
    args, cotangents = one_chunk(dtype, n=128)
    found = products(gdn._chunk_bwd, *args, *cotangents) if backward else products(gdn._chunk_fwd, *args)
    six = [types for exact, types, _ in found if exact]
    rest = [types for exact, types, _ in found if not exact]
    assert all(types == ("float32", "float32") for types in six)
    assert all(types == ("bfloat16", "bfloat16") for types in rest)
    if dtype == jnp.float32:
        assert (len(six), len(rest)) == ((12 + 18, 0) if backward else (12 + 7, 0))
    else:  # a three-pass product is three of bf16 parts; K K^T and Q K^T one each
        assert (len(six), len(rest)) == ((12 + 5, 3 * 11 + 2) if backward else (12 + 2, 3 * 3 + 2))
    # ... and the passes they come to are what `mxu_passes` says (and `chunk_flops` tells XLA).
    passes = sum((6 if exact else 1) * size for exact, _, size in found) / 128 ** 3
    assert np.isclose(passes, gdn.mxu_passes(128, 96, 192, dtype, backward))


@pytest.mark.parametrize("dims", ("NN", "NT", "TN"))
@pytest.mark.parametrize("side", ("left", "right"))
def test_a_three_pass_product_of_a_bf16_operand_is_the_f32_product_of_its_cast(dims, side):
    """`_mm` of a bf16 array and an f32 one is the bf16 array against the f32 one's three bf16 parts, which sum
    to it to 2^-24: the product of the cast at full precision, to f32 rounding; one pass of the f32 array
    rounded to bf16 reads a thousand times further."""
    keys = jax.random.split(jax.random.PRNGKey(11), 2)
    shapes = {"NN": ((64, 96), (96, 192)), "NT": ((64, 96), (192, 96)), "TN": ((96, 64), (96, 192))}[dims]
    a, b = (jax.random.normal(key, shape) for key, shape in zip(keys, shapes))
    a, b = (a.astype(jnp.bfloat16), b) if side == "left" else (a, b.astype(jnp.bfloat16))
    dims = getattr(gdn, dims)
    want = gdn._mm(a.astype(jnp.float32), b.astype(jnp.float32), dims)
    assert far(gdn._mm(a, b, dims), want) < 2e-6
    assert far(gdn._mm(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), dims), want) > 1e-3
    parts = gdn._bf16_parts(b if side == "left" else a)
    assert all(part.dtype == jnp.bfloat16 for part in parts)
    assert far(sum(part.astype(jnp.float32) for part in parts), b if side == "left" else a) < 2 ** -22


@pytest.mark.parametrize("heads, seq, want", ((30, 4096, 3), (15, 4096, 3), (4, 4096, 2), (1, 4096, 1), (7, 4096, 1),
                                              (30, 1 << 16, 2), (30, 1 << 17, 1)))
def test_the_heads_a_program_walks_follow_the_heads_held_and_the_vmem_they_need(heads, seq, want):
    """A divisor of the heads the call holds, three at most (30 on a device of the cell; 15 under tensor=2), 1 where
    nothing divides them; fewer where a head's blocks grow (its gates are a whole row's: 131,072 positions leave
    room for one head, 65,536 for two)."""
    assert gdn.heads_per_program(heads, seq, 128, 96, 192, 2) == want
