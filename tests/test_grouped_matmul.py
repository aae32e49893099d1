"""The grouped-matmul kernels of `ops/grouped_matmul.py` in interpret mode
against their XLA form (`jax.lax.ragged_dot`), forward and both gradients,
and the visit schedule they walk."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import grouped_matmul as gm

# 512 rows in 5 groups over row tiles of 256: a boundary inside a tile, empty
# groups first, last and between, everything on one group, single rows.
GROUPINGS = {
    "uneven_with_an_empty_group": [100, 0, 300, 12, 100],
    "all_on_one_group": [0, 0, 512, 0, 0],
    "on_tile_boundaries": [256, 256, 0, 0, 0],
    "single_rows_then_the_rest": [1, 1, 1, 1, 508],
    "empty_groups_first": [0, 0, 0, 1, 511],
}


def _operands(dtype, m=512, k=256, n=384, g=5):
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    make = lambda key, shape: jax.random.normal(key, shape, jnp.float32).astype(dtype)
    return make(keys[0], (m, k)), make(keys[1], (g, k, n)), make(keys[2], (m, n))


def _forward_and_gradients(backend, lhs, rhs, sizes, dout):
    out, vjp = jax.vjp(lambda a, b: gm.grouped_matmul(a, b, sizes, backend=backend, interpret=True),
                       lhs, rhs)
    return (out, *vjp(dout))


@pytest.mark.parametrize("grouping", sorted(GROUPINGS))
def test_the_kernels_agree_with_the_xla_form_in_float32(grouping):
    sizes = jnp.asarray(GROUPINGS[grouping], jnp.int32)
    lhs, rhs, dout = _operands(jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = _forward_and_gradients("pallas", lhs, rhs, sizes, dout)
        want = _forward_and_gradients("xla", lhs, rhs, sizes, dout)
    for name, a, b in zip(("out", "dlhs", "drhs"), got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-3, err_msg=name)
    # The gradient of a group with no rows is written, as zeros.
    empty = np.asarray(sizes) == 0
    assert not np.asarray(got[2])[empty].any()


def test_in_bf16_they_agree_to_bf16_rounding():
    sizes = jnp.asarray(GROUPINGS["uneven_with_an_empty_group"], jnp.int32)
    lhs, rhs, dout = _operands(jnp.bfloat16)
    got = _forward_and_gradients("pallas", lhs, rhs, sizes, dout)
    want = _forward_and_gradients("xla", lhs, rhs, sizes, dout)
    for a, b in zip(got, want):
        assert a.dtype == jnp.bfloat16
        # One bf16 rounding of sums of 256 to 512 products of unit normals.
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   rtol=2e-2, atol=0.5)


def test_the_visits_cover_every_row_once_and_pad_with_the_last():
    sizes = jnp.asarray(GROUPINGS["uneven_with_an_empty_group"], jnp.int32)
    group_ids, tile_ids, starts, ends, num = (np.asarray(x) for x in gm._visits(sizes, 512, 256, False))
    assert list(starts) == [0, 100, 100, 400, 412] and list(ends) == [100, 100, 400, 412, 512]
    # Group 0 in tile 0; group 2 in tiles 0 and 1; groups 3 and 4 in tile 1; the empty one nowhere.
    assert num[0] == 5 and len(group_ids) == 512 // 256 + 5
    assert list(zip(group_ids[:5], tile_ids[:5])) == [(0, 0), (2, 0), (2, 1), (3, 1), (4, 1)]
    assert set(zip(group_ids[5:], tile_ids[5:])) == {(4, 1)}
    with_empty = gm._visits(sizes, 512, 256, True)
    assert int(with_empty[4][0]) == 6 and list(np.asarray(with_empty[0])[:6]) == [0, 1, 2, 2, 3, 4]


def test_tiles_follow_from_the_shapes():
    # OLMoE's three products: a group's whole matrix is one block of 4 MiB.
    assert gm._tiles(65536, 2048, 1024, 2) == (256, 1024, 2048, 512, 1024, 1024)
    assert gm._tiles(65536, 1024, 2048, 2) == (256, 2048, 1024, 512, 1024, 1024)
    assert gm._tiles(65536, 8192, 1024, 2).out_fwd == 256  # a wider contraction, a narrower block
    assert gm._tiles(100, 256, 256, 2) is None and gm._tiles(512, 200, 256, 2) is None


def test_what_does_not_tile_runs_the_xla_form_or_says_so():
    lhs, rhs = jnp.ones((100, 64)), jnp.ones((4, 64, 32))
    sizes = jnp.asarray([10, 20, 30, 40], jnp.int32)
    out = gm.grouped_matmul(lhs, rhs, sizes)
    np.testing.assert_allclose(out, 64.0)
    with pytest.raises(ValueError, match="does not tile"):
        gm.grouped_matmul(lhs, rhs, sizes, backend="pallas")
    with pytest.raises(ValueError, match="lhs is"):
        gm.grouped_matmul(lhs.astype(jnp.bfloat16), rhs, sizes)
