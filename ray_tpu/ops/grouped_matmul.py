"""Grouped matrix multiplication for mixture-of-experts layers.

`grouped_matmul(lhs, rhs, group_sizes)`: the rows of `lhs` (m, k) come in
`g` consecutive groups of `group_sizes` rows (summing to m), and row i is
multiplied by the matrix of its group, `rhs[group(i)]` (k, n). What
`jax.lax.ragged_dot` computes, and off the TPU that is what runs; on the TPU
three Pallas kernels of the repo's own do, because XLA's lowering of
`ragged_dot` (Mosaic kernels at fixed 512-tiles) stays under half of the bf16
peak at an expert layer's shapes and gives its instructions no `op_name`, so
no trace can say what they belong to (PERF.md section 6, PR 28):

- `gmm_fwd`: one program per (tile of n, visit). A visit is a (group, tile of
  rows) pair: a row tile that straddles a group boundary is visited once for
  each group it holds rows of, and each visit stores only its group's rows.
  The whole contraction dimension is one block, so a group's matrix is
  fetched once and stays in VMEM while the grid walks the group's row tiles.
- `gmm_dlhs`: the gradient for the rows, the same kernel against the
  transposed matrices (`dout @ rhs[group]^T`, contracting the last dimension
  of both).
- `gmm_drhs`: the gradient for the matrices, per group `lhs_rows^T @
  dout_rows`, accumulated in float32 in VMEM over the group's visits and
  written when the grid leaves the group. A group with no rows gets one
  visit with nothing in it, so its gradient is written as zeros.

Which visits there are is computed from `group_sizes` by a few XLA operations
(`_visits`) and handed to the kernels as scalar-prefetch arguments; the grid
has the static upper bound of `m / tile + g` visits, and the ones past the
last real visit repeat its block indices and do nothing. Tile sizes follow
from the shapes alone (`_tiles`).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# A block of the grouped operand (the whole contraction dimension by a tile of
# the other) may take this much of VMEM, twice for its two buffers; with the
# row tiles and the result that keeps a program under Mosaic's default 16 MiB.
RHS_BLOCK_BYTES = 4 * 1024 * 1024
ROW_TILES = (256, 128)  # forward and dlhs: rows a visit multiplies
DRHS_ROW_TILES = (512, 256, 128)  # drhs: rows a visit contracts over
DRHS_TILE = 1024  # drhs: the float32 accumulator is at most DRHS_TILE^2


class Tiles(NamedTuple):
    rows: int  # of lhs and out, forward and dlhs
    out_fwd: int  # forward: the whole of k is one block, by this tile of n
    out_dlhs: int  # dlhs: the whole of n is one block, by this tile of k
    drhs_rows: int
    drhs_k: int
    drhs_n: int


def _divisor(size: int, limit: int, step: int = 128) -> Optional[int]:
    """The largest multiple of `step` that divides `size` and is at most `limit`."""
    for t in range(min(size, limit) // step * step, 0, -step):
        if size % t == 0:
            return t
    return None


def _tiles(m: int, k: int, n: int, itemsize: int) -> Optional[Tiles]:
    """The tile sizes for `(m, k) x (g, k, n)`, or nothing where the shapes do
    not tile (the XLA form runs then)."""
    rows = next((t for t in ROW_TILES if m % t == 0), None)
    drhs_rows = next((t for t in DRHS_ROW_TILES if m % t == 0), None)
    out_fwd = _divisor(n, RHS_BLOCK_BYTES // (k * itemsize))
    out_dlhs = _divisor(k, RHS_BLOCK_BYTES // (n * itemsize))
    drhs_k, drhs_n = _divisor(k, DRHS_TILE), _divisor(n, DRHS_TILE)
    if None in (rows, drhs_rows, out_fwd, out_dlhs, drhs_k, drhs_n):
        return None
    return Tiles(rows, out_fwd, out_dlhs, drhs_rows, drhs_k, drhs_n)


def _visits(group_sizes, m: int, tile: int, visit_empty: bool):
    """The (group, row tile) pairs a kernel walks, in order: `group_ids` and
    `tile_ids` (each `m / tile + g` long; entries past `num` repeat the last
    real visit), the groups' first and one-past-last rows, and `num`, the
    number of real visits. With `visit_empty`, a group of no rows is visited
    once (at a tile it touches no row of)."""
    g = group_sizes.shape[0]
    n_tiles = m // tile
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = jnp.minimum(starts // tile, n_tiles - 1)
    last = jnp.where(sizes > 0, (ends - 1) // tile, first - (0 if visit_empty else 1))
    count = last - first + 1
    visit_ends = jnp.cumsum(count)
    num = visit_ends[-1]
    v = jnp.minimum(jnp.arange(n_tiles + g, dtype=jnp.int32), num - 1)
    group_ids = jnp.searchsorted(visit_ends, v, side="right").astype(jnp.int32)
    tile_ids = first[group_ids] + v - (visit_ends - count)[group_ids]
    return group_ids, tile_ids.astype(jnp.int32), starts, ends, num.reshape(1)


def _row_mask(tile_id, start, end, rows: int):
    """(rows, 1): which rows of row tile `tile_id` lie in [start, end)."""
    row = tile_id * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    return (row >= start) & (row < end)


# ------------------------------------------------------------------ forward, dlhs
def _gmm_kernel(group_ids, tile_ids, starts, ends, num, lhs_ref, rhs_ref, out_ref, *,
                rows: int, transpose_rhs: bool):
    v = pl.program_id(1)

    @pl.when(v < num[0])
    def _():
        group = group_ids[v]
        contract = (((1,), (1,)), ((), ())) if transpose_rhs else (((1,), (0,)), ((), ()))
        acc = jax.lax.dot_general(lhs_ref[...], rhs_ref[0], contract,
                                  preferred_element_type=jnp.float32)
        mine = _row_mask(tile_ids[v], starts[group], ends[group], rows)
        # The rows of other groups in this tile were stored by the visits
        # before this one, or will be by the ones after it.
        out_ref[...] = jnp.where(mine, acc.astype(out_ref.dtype), out_ref[...])


def _gmm(lhs, rhs, group_sizes, rows: int, out_tile: int, transpose_rhs: bool, interpret: bool):
    m, contraction = lhs.shape
    g = rhs.shape[0]
    out_dim = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    if transpose_rhs:
        rhs_spec = pl.BlockSpec((1, out_tile, contraction), lambda j, v, gi, ti, *_: (gi[v], j, 0))
    else:
        rhs_spec = pl.BlockSpec((1, contraction, out_tile), lambda j, v, gi, ti, *_: (gi[v], 0, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, rows=rows, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(out_dim // out_tile, m // rows + g),
            in_specs=[
                pl.BlockSpec((rows, contraction), lambda j, v, gi, ti, *_: (ti[v], 0)),
                rhs_spec,
            ],
            out_specs=pl.BlockSpec((rows, out_tile), lambda j, v, gi, ti, *_: (ti[v], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, out_dim), lhs.dtype),
        interpret=interpret,
        name="gmm_dlhs" if transpose_rhs else "gmm_fwd",
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(*_visits(group_sizes, m, rows, visit_empty=False), lhs, rhs)


# --------------------------------------------------------------------------- drhs
def _drhs_kernel(group_ids, tile_ids, starts, ends, num, lhs_ref, dout_ref, out_ref, acc, *,
                 rows: int):
    v = pl.program_id(2)
    last_visit = num[0] - 1
    group = group_ids[v]

    @pl.when(v <= last_visit)
    def _():
        @pl.when((v == 0) | (group_ids[jnp.maximum(v - 1, 0)] != group))
        def _():
            acc[...] = jnp.zeros_like(acc)

        mine = _row_mask(tile_ids[v], starts[group], ends[group], rows)
        lhs = jnp.where(mine, lhs_ref[...], jnp.zeros_like(lhs_ref[...]))
        acc[...] += jax.lax.dot_general(lhs, dout_ref[...], (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

        @pl.when((v == last_visit) | (group_ids[jnp.minimum(v + 1, last_visit)] != group))
        def _():
            out_ref[0] = acc[...].astype(out_ref.dtype)


def _drhs(lhs, dout, group_sizes, g: int, rows: int, tile_k: int, tile_n: int, interpret: bool):
    m, k = lhs.shape
    n = dout.shape[1]
    return pl.pallas_call(
        functools.partial(_drhs_kernel, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(k // tile_k, n // tile_n, m // rows + g),
            in_specs=[
                pl.BlockSpec((rows, tile_k), lambda i, j, v, gi, ti, *_: (ti[v], i)),
                pl.BlockSpec((rows, tile_n), lambda i, j, v, gi, ti, *_: (ti[v], j)),
            ],
            out_specs=pl.BlockSpec((1, tile_k, tile_n), lambda i, j, v, gi, ti, *_: (gi[v], i, j)),
            scratch_shapes=[pltpu.VMEM((tile_k, tile_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((g, k, n), lhs.dtype),
        interpret=interpret,
        name="gmm_drhs",
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*_visits(group_sizes, m, rows, visit_empty=True), lhs, dout)


# --------------------------------------------------------------------- the product
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _pallas_grouped_matmul(lhs, rhs, group_sizes, interpret: bool):
    tiles = _tiles(lhs.shape[0], rhs.shape[1], rhs.shape[2], lhs.dtype.itemsize)
    return _gmm(lhs, rhs, group_sizes, tiles.rows, tiles.out_fwd, False, interpret)


def _fwd_rule(lhs, rhs, group_sizes, interpret):
    return _pallas_grouped_matmul(lhs, rhs, group_sizes, interpret), (lhs, rhs, group_sizes)


def _bwd_rule(interpret, res, dout):
    lhs, rhs, group_sizes = res
    tiles = _tiles(lhs.shape[0], rhs.shape[1], rhs.shape[2], lhs.dtype.itemsize)
    dout = dout.astype(lhs.dtype)
    dlhs = _gmm(dout, rhs, group_sizes, tiles.rows, tiles.out_dlhs, True, interpret)
    drhs = _drhs(lhs, dout, group_sizes, rhs.shape[0], tiles.drhs_rows, tiles.drhs_k,
                 tiles.drhs_n, interpret)
    return dlhs, drhs.astype(rhs.dtype), None


_pallas_grouped_matmul.defvjp(_fwd_rule, _bwd_rule)


def xla_grouped_matmul(lhs, rhs, group_sizes):
    """The XLA form: what runs off the TPU, and the kernels' test reference."""
    return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32))


def grouped_matmul(lhs, rhs, group_sizes, backend: Optional[str] = None, interpret: bool = False):
    """`out[i] = lhs[i] @ rhs[group of row i]` for `lhs` (m, k) whose rows lie
    in `g` consecutive groups of `group_sizes` rows, `rhs` (g, k, n).
    `group_sizes` must sum to m. Differentiable in `lhs` and `rhs`.

    backend: "pallas" | "xla" | None: the kernels where the computation is
    lowered for a TPU and the shapes tile (rows, k and n multiples of 128),
    else the XLA form."""
    if lhs.dtype != rhs.dtype:
        raise ValueError(f"grouped_matmul: lhs is {lhs.dtype}, rhs is {rhs.dtype}")
    tiled = _tiles(lhs.shape[0], rhs.shape[1], rhs.shape[2], lhs.dtype.itemsize) is not None
    if backend == "pallas" and not tiled:
        raise ValueError(
            f"grouped_matmul(backend='pallas'): {lhs.shape} x {rhs.shape} does not tile: "
            "rows, k and n must be multiples of 128")
    if backend == "xla" or not tiled:
        return xla_grouped_matmul(lhs, rhs, group_sizes)
    pallas = functools.partial(_pallas_grouped_matmul, interpret=interpret)
    if backend == "pallas":
        return pallas(lhs, rhs, group_sizes)
    return jax.lax.platform_dependent(lhs, rhs, group_sizes, tpu=pallas, default=xla_grouped_matmul)
