"""The grouped-matmul kernels of `ops/grouped_matmul.py` in interpret mode
against their XLA form (`jax.lax.ragged_dot`), forward and both gradients,
the visit schedule they walk, and the rows of products they issue."""

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
# 1,024 rows in 5 groups: `gmm_fwd` / `gmm_dlhs` walk 4 row tiles of 256 in blocks of
# 64 rows, `gmm_drhs` 2 tiles of 512 in blocks of 128. Where the first boundary falls:
GROUPINGS.update({
    "boundary_on_a_block_edge_of_all_three": [384, 640, 0, 0, 0],
    "boundary_one_row_before_a_block_edge": [383, 641, 0, 0, 0],
    "boundary_one_row_after_a_block_edge": [385, 639, 0, 0, 0],
    "boundary_on_a_64_row_edge_inside_a_128_row_block": [320, 704, 0, 0, 0],
    "boundary_on_the_edge_of_a_256_row_tile": [256, 768, 0, 0, 0],
    "boundary_on_the_edge_of_every_tile": [512, 512, 0, 0, 0],
    "two_boundaries_inside_one_block": [300, 20, 704, 0, 0],
    "a_group_of_twelve_rows_across_a_tile_edge": [250, 12, 762, 0, 0],
    "groups_smaller_than_a_block_one_after_another": [30, 30, 30, 30, 904],
    "an_empty_group_first": [0, 400, 624, 0, 0],
    "an_empty_group_last": [400, 100, 100, 424, 0],
    "an_empty_group_in_the_middle_of_a_block": [400, 0, 100, 0, 524],
})


def _operands(dtype, m, k=256, n=384, g=5):
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
    lhs, rhs, dout = _operands(jnp.float32, sum(GROUPINGS[grouping]))
    with jax.default_matmul_precision("highest"):
        got = _forward_and_gradients("pallas", lhs, rhs, sizes, dout)
        want = _forward_and_gradients("xla", lhs, rhs, sizes, dout)
    for name, a, b in zip(("out", "dlhs", "drhs"), got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-3, err_msg=name)
    # The gradient of a group with no rows is written, as zeros.
    empty = np.asarray(sizes) == 0
    assert not np.asarray(got[2])[empty].any()


@pytest.mark.parametrize("grouping", sorted(GROUPINGS))
def test_in_bf16_they_agree_to_bf16_rounding(grouping):
    sizes = jnp.asarray(GROUPINGS[grouping], jnp.int32)
    lhs, rhs, dout = _operands(jnp.bfloat16, sum(GROUPINGS[grouping]))
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


@pytest.mark.parametrize("kernel", ["gmm_fwd", "gmm_dlhs", "gmm_drhs"])
def test_every_tile_of_the_result_walks_the_visits_again(kernel):
    """With the result in several tiles (the outer grid dimensions) each pass
    starts its own copies of the groups' matrices and its own accumulator."""
    sizes = jnp.asarray(GROUPINGS["two_boundaries_inside_one_block"], jnp.int32)
    lhs, rhs, dout = _operands(jnp.float32, 1024)
    with jax.default_matmul_precision("highest"):
        out, dlhs, drhs = _forward_and_gradients("xla", lhs, rhs, sizes, dout)
        got, want = {
            "gmm_fwd": lambda: (gm._gmm(lhs, rhs, sizes, 256, 128, False, True), out),
            "gmm_dlhs": lambda: (gm._gmm(dout, rhs, sizes, 256, 128, True, True), dlhs),
            "gmm_drhs": lambda: (gm._drhs(lhs, dout, sizes, 5, 512, 128, 128, True), drhs),
        }[kernel]()
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


def _cell_group_sizes(seed):
    # The OLMoE cell's expert layer: 2 x 4,096 tokens, 8 of 64 experts each, a random router.
    return np.random.default_rng(seed).multinomial(65536, np.full(64, 1 / 64))


@pytest.mark.parametrize("kernel, tile, sub", [
    ("gmm_fwd", 256, gm.SUB_ROWS), ("gmm_dlhs", 256, gm.SUB_ROWS), ("gmm_drhs", 512, gm.DRHS_SUB_ROWS)])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a_call_issues_at_most_a_block_of_rows_more_for_each_group(kernel, tile, sub, seed):
    """The mechanism's counter. A visit multiplies the blocks of its tile that
    hold rows of its group, so 63 boundaries cost at most 63 blocks of rows
    beyond the 65,536 the routing asks for; multiplying whole tiles cost a
    tile each (+ 24.6 % at 256 rows, + 49 % at 512)."""
    sizes = _cell_group_sizes(seed)
    m, g = int(sizes.sum()), len(sizes)
    assert m <= gm.issued_rows(sizes, sub) <= m + g * sub
    assert gm.issued_rows(sizes, tile) >= m + (g - 4) * tile  # the whole-tile form
    # ... and `issued_rows` is what the kernel's visits add up to.
    group_ids, tile_ids, starts, ends, num = (
        np.asarray(x) for x in gm._visits(jnp.asarray(sizes, jnp.int32), m, tile, kernel == "gmm_drhs"))
    real = slice(0, int(num[0]))
    *_, count = gm._live_window(tile_ids[real], starts[group_ids[real]], ends[group_ids[real]], tile, sub)
    assert int(np.sum(count)) * sub == gm.issued_rows(sizes, sub)


@pytest.mark.parametrize("grouping", sorted(GROUPINGS))
def test_issued_rows_counts_a_shared_block_once_for_each_group_in_it(grouping):
    sizes = np.asarray(GROUPINGS[grouping])
    for sub in (gm.SUB_ROWS, gm.DRHS_SUB_ROWS):
        want = sum(len({row // sub for row in range(end - size, end)})
                   for size, end in zip(sizes, np.cumsum(sizes))) * sub
        assert gm.issued_rows(sizes, sub) == want <= sizes.sum() + len(sizes) * sub


def test_the_matrix_slots_alternate_over_the_groups_that_have_rows():
    slots, nexts = gm._matrix_slots(jnp.asarray([0, 5, 0, 0, 7, 1, 0], jnp.int32))
    assert [int(slots[i]) for i in (1, 4, 5)] == [0, 1, 0]
    assert list(np.asarray(nexts)) == [1, 4, 4, 4, 5, 7, 7]  # 7: no group with rows is left


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


# ------------------------------------------------ groups that end before the rows do
# 1,024 rows of which the groups hold fewer (`short=True`): an expert layer that holds some of
# the experts sorts the other experts' pairs behind its own groups (`models/moe.py`).
SHORT = {
    "an_eighth_held": [60, 40, 0, 28, 0],
    "held_rows_end_inside_a_tile": [300, 20, 50, 0, 0],
    "held_rows_end_on_a_tile_edge": [256, 200, 56, 0, 0],
    "all_but_one_row": [1000, 23, 0, 0, 0],
    "one_row": [0, 0, 1, 0, 0],
    "none": [0, 0, 0, 0, 0],
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("grouping", sorted(SHORT))
def test_rows_past_the_last_group_are_multiplied_by_nothing(grouping, dtype):
    """Forward and both gradients against `ragged_dot` on the rows the groups
    hold; the gradient of the matrices gets nothing from the rows past them,
    although `lhs` and `dout` are not zero there."""
    sizes = jnp.asarray(SHORT[grouping], jnp.int32)
    held = sum(SHORT[grouping])
    lhs, rhs, dout = _operands(jnp.dtype(dtype), 1024)

    def run(backend):
        out, vjp = jax.vjp(lambda a, b: gm.grouped_matmul(
            a, b, sizes, backend=backend, interpret=True, short=True), lhs, rhs)
        return (out, *vjp(dout))

    with jax.default_matmul_precision("highest"):
        got, want = run("pallas"), run("xla")
        # What the held rows alone give: the XLA form itself adds nothing for the others.
        alone = _forward_and_gradients("xla", lhs[:held], rhs, sizes, dout[:held])
    tol = dict(rtol=1e-4, atol=1e-3) if dtype == "float32" else dict(rtol=2e-2, atol=0.5)
    f32 = lambda a: np.asarray(a, np.float32)
    for name, a, b, c in zip(("out", "dlhs"), got, want, alone):
        np.testing.assert_allclose(f32(a)[:held], f32(b)[:held], err_msg=name, **tol)
        np.testing.assert_allclose(f32(b)[:held], f32(c), err_msg=name, **tol)
        assert not f32(b)[held:].any()  # the XLA form writes zeros there; the kernels nothing
    np.testing.assert_allclose(f32(got[2]), f32(want[2]), err_msg="drhs", **tol)
    np.testing.assert_allclose(f32(want[2]), f32(alone[2]), err_msg="drhs", **tol)


@pytest.mark.parametrize("grouping", sorted(SHORT))
def test_no_visit_and_no_issued_row_for_the_rows_past_the_last_group(grouping):
    sizes = np.asarray(SHORT[grouping])
    held = int(sizes.sum())
    for tile, sub, visit_empty in ((256, gm.SUB_ROWS, False), (512, gm.DRHS_SUB_ROWS, True)):
        group_ids, tile_ids, starts, ends, num = (
            np.asarray(x) for x in gm._visits(jnp.asarray(sizes, jnp.int32), 1024, tile, visit_empty, True))
        real = slice(0, int(num[0]))
        # No visit of a tile that lies wholly past the held rows (an empty group's one visit in
        # `gmm_drhs`, which multiplies nothing, is at the tile its start falls in).
        assert (tile_ids[real] <= held // tile).all()
        assert ((0 <= tile_ids) & (tile_ids < 1024 // tile)).all()
        assert ((0 <= group_ids) & (group_ids < len(sizes))).all()
        *_, count = gm._live_window(tile_ids[real], starts[group_ids[real]], ends[group_ids[real]], tile, sub)
        assert int(np.sum(count)) * sub == gm.issued_rows(sizes, sub) <= held + len(sizes) * sub
