"""Grouped matrix multiplication for mixture-of-experts layers.

`grouped_matmul(lhs, rhs, group_sizes)`: the rows of `lhs` (m, k) come in
`g` consecutive groups of `group_sizes` rows (summing to m, or with
`short=True` to fewer: the rows past the last group are nobody's), and row i is
multiplied by the matrix of its group, `rhs[group(i)]` (k, n). What
`jax.lax.ragged_dot` computes, and off the TPU that is what runs; on the TPU
three Pallas kernels of the repo's own do, because XLA's lowering of
`ragged_dot` (Mosaic kernels at fixed 512-tiles) stays under half of the bf16
peak at an expert layer's shapes and gives its instructions no `op_name`, so
no trace can say what they belong to (PERF.md section 6, PR 28):

- `gmm_fwd`: one program per (tile of n, visit). A visit is a (group, tile of
  rows) pair: a row tile that straddles a group boundary is visited once for
  each group it holds rows of. A visit multiplies the window of `SUB_ROWS`-row
  blocks of its tile that hold rows of its group, and no other: the whole
  tile where the tile lies inside the group, one to three blocks at a
  dynamic start where the group begins or ends inside it, one product of a
  static size either way (`_for_live_window`), and it stores only its group's
  rows. The whole contraction dimension is one block. A group's matrix is
  not a pipelined operand: the kernel copies it from HBM into one of two VMEM
  slots itself, starting at the second visit of the group before (a whole
  group ahead; the pipeline would start at the group's last visit, which at a
  boundary is a short one, and the next group's first visit waited for 4 MiB).
- `gmm_dlhs`: the gradient for the rows, the same kernel against the
  transposed matrices (`dout @ rhs[group]^T`, contracting the last dimension
  of both).
- `gmm_drhs`: the gradient for the matrices, per group `lhs_rows^T @
  dout_rows`, accumulated in float32 in VMEM over the group's visits, each
  contracting over the window of live `DRHS_SUB_ROWS`-row blocks of its tile
  in one product and one update of the accumulator, with the rows of other
  groups in the window zeroed. The result is stored at the visit after the
  group's last (its block stays the output block for that one visit more),
  so that its write-back runs beside a whole visit and not beside the next
  group's short first one. A group with no rows gets one visit with no
  product in it, so its gradient is written as zeros.

Which visits there are is computed from `group_sizes` by a few XLA operations
(`_visits`, `_matrix_slots`) and handed to the kernels as scalar-prefetch
arguments; the grid has the static upper bound of `m / tile + g` visits (at
least one more than there are), and the ones past the last real visit repeat
its block indices and multiply nothing. Tile sizes follow from the shapes
alone (`_tiles`). `issued_rows` counts the rows of products a call issues:
at most `m + g * sub`, where multiplying whole tiles issued `m + g * tile`.

Rows past the last group (`short=True`: an expert layer that holds some of the
experts sorts the pairs of the others there, `models/moe.py`) get no visit in
any of the three kernels: nothing is multiplied for them, `issued_rows` counts
nothing for them, and they add nothing to a group's `drhs`. Their rows of the
result (and of `dlhs`) are therefore never written by the kernels and hold
whatever the buffer held, where `ragged_dot` writes zeros: a caller must not
read them (`moe_mlp` masks them where it reads a whole array, and `sum_rows`
is never pointed at them).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# A block of the grouped operand (the whole contraction dimension by a tile of
# the other) may take this much of VMEM, twice for its two buffers; with the
# row tiles and the result that keeps a program under Mosaic's default 16 MiB.
RHS_BLOCK_BYTES = 4 * 1024 * 1024
ROW_TILES = (256, 128)  # forward and dlhs: rows of a visit's tile
DRHS_ROW_TILES = (512, 256, 128)  # drhs: rows of a visit's tile
DRHS_TILE = 1024  # drhs: the float32 accumulator is at most DRHS_TILE^2
# The blocks a visit's window is made of. On a v5e at OLMoE's shapes (PERF.md
# section 6, PR 33): 64 rows read 4 % under 128 in `gmm_fwd` / `gmm_dlhs` and 32
# another 1 %; in `gmm_drhs`, where the rows are the contraction, 64 read as 128.
SUB_ROWS = 64
DRHS_SUB_ROWS = 128


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


# `inline=True`: traced once for its arguments' shapes and then replayed into
# the caller's trace, with no call of its own in the program.
@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4), inline=True)
def _visits(group_sizes, m: int, tile: int, visit_empty: bool, short: bool = False):
    """The (group, row tile) pairs a kernel walks, in order: `group_ids` and
    `tile_ids` (each `m / tile + g` long; entries past `num` repeat the last
    real visit), the groups' first and one-past-last rows, and `num`, the
    number of real visits. With `visit_empty`, a group of no rows is visited
    once (at a tile it touches no row of). With `short` the groups may hold
    fewer than m rows, even none: then there may be no visit at all, and the
    entries name the last group and a tile inside the array all the same."""
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
    if short:
        v, group_ids = jnp.maximum(v, 0), jnp.minimum(group_ids, g - 1)
    tile_ids = first[group_ids] + v - (visit_ends - count)[group_ids]
    return group_ids, tile_ids.astype(jnp.int32), starts, ends, num.reshape(1)


@functools.partial(jax.jit, inline=True)
def _matrix_slots(group_sizes):
    """For `_gmm_kernel`'s own copies of the groups' matrices, by group: the
    VMEM slot (0 / 1, alternating over the groups that have rows) and the
    next group that has rows (g where there is none)."""
    g = group_sizes.shape[0]
    visited = group_sizes > 0
    slots = (jnp.cumsum(visited) - 1) % 2
    later = jax.lax.cummin(jnp.where(visited, jnp.arange(g), g), reverse=True)
    return slots.astype(jnp.int32), jnp.append(later[1:], g).astype(jnp.int32)


def issued_rows(group_sizes, sub: int) -> int:
    """The rows of products a kernel issues for concrete `group_sizes` when
    its visits multiply windows of `sub`-row blocks: each group pays for the
    blocks that hold a row of it, so a block two groups share is paid twice.
    At most `sum(group_sizes) + g * sub`."""
    sizes = np.asarray(group_sizes, np.int64)
    ends = np.cumsum(sizes)
    blocks = -(-ends // sub) - (ends - sizes) // sub
    return int(blocks[sizes > 0].sum()) * sub


def _live_window(tile_id, start, end, rows: int, sub: int):
    """Of row tile `tile_id`, what belongs to the group of rows [start, end):
    its first and one-past-last row, counted from the tile's first, and the
    `sub`-row blocks that hold them: the first one and how many (none for a
    group with no rows in the tile)."""
    lo = jnp.clip(start - tile_id * rows, 0, rows)
    hi = jnp.clip(end - tile_id * rows, lo, rows)
    first = lo // sub
    return lo, hi, first, jnp.where(hi > lo, (hi + sub - 1) // sub - first, 0)


def _for_live_window(tile_id, start, end, rows: int, sub: int, body):
    """Runs `body(window, mine)` on the `_live_window` of a visit: `window`
    indexes the tile's rows (the whole tile, or `n * sub` rows from a block
    edge, `n` static), `mine` (rows of the window, 1) says which of them are
    the group's. Nothing runs for a group with no rows in the tile."""
    lo, hi, first, count = _live_window(tile_id, start, end, rows, sub)
    for n in range(1, rows // sub + 1):
        @pl.when(count == n)
        def _(n=n):
            whole = n * sub == rows  # then first == 0
            offset = 0 if whole else pl.multiple_of(first * sub, sub)
            row = offset + jax.lax.broadcasted_iota(jnp.int32, (n * sub, 1), 0)
            body(slice(None) if whole else pl.ds(offset, n * sub), (row >= lo) & (row < hi))


# ------------------------------------------------------------------ forward, dlhs
def _gmm_kernel(group_ids, tile_ids, starts, ends, num, slots, nexts, lhs_ref, rhs_hbm, out_ref,
                rhs_buf, sem, *, rows: int, out_tile: int, transpose_rhs: bool):
    j, v = pl.program_id(0), pl.program_id(1)
    last_visit = num[0] - 1

    def matrix_copy(group, slot):
        block = pl.ds(pl.multiple_of(j * out_tile, out_tile), out_tile)
        src = rhs_hbm.at[group, block, :] if transpose_rhs else rhs_hbm.at[group, :, block]
        return pltpu.make_async_copy(src, rhs_buf.at[slot], sem.at[slot])

    @pl.when(v <= last_visit)
    def _():
        group = group_ids[v]
        slot = slots[group]
        first = (v == 0) | (group_ids[jnp.maximum(v - 1, 0)] != group)
        second = jnp.logical_not(first) & ((v == 1) | (group_ids[jnp.maximum(v - 2, 0)] != group))
        only = first & ((v == last_visit) | (group_ids[jnp.minimum(v + 1, last_visit)] != group))

        @pl.when(v == 0)
        def _():
            matrix_copy(group, slot).start()

        @pl.when(first)
        def _():
            matrix_copy(group, slot).wait()

        # The other slot is free from this group's first visit on. Not there: it is
        # a short one where the group starts inside a tile, and the copy would
        # delay the next tile's rows.
        @pl.when((second | only) & (nexts[group] < rhs_hbm.shape[0]))
        def _():
            matrix_copy(nexts[group], 1 - slot).start()

        def multiply(window, mine):
            contract = (((1,), (1,)), ((), ())) if transpose_rhs else (((1,), (0,)), ((), ()))
            acc = jax.lax.dot_general(lhs_ref[window, :], rhs_buf[slot], contract,
                                      preferred_element_type=jnp.float32)
            # The rows of other groups in this tile were stored by the visits
            # before this one, or will be by the ones after it.
            out_ref[window, :] = jnp.where(mine, acc.astype(out_ref.dtype), out_ref[window, :])

        _for_live_window(tile_ids[v], starts[group], ends[group], rows, SUB_ROWS, multiply)


@functools.lru_cache(maxsize=None)
def _gmm_call(m: int, contraction: int, g: int, out_dim: int, dtype, rows: int, out_tile: int,
              transpose_rhs: bool, interpret: bool):
    """The `pallas_call` for these shapes, made once a process: a call of the same
    object again reuses the kernel's traced body, where a new `pallas_call` traces
    all its windows' products anew at each of a step's nine sites, every time a
    run lowers the step (PERF.md section 6, PR 33: 5.5 s of a warm set-up)."""
    matrix_block = (out_tile, contraction) if transpose_rhs else (contraction, out_tile)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, rows=rows, out_tile=out_tile, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(out_dim // out_tile, m // rows + g),
            in_specs=[
                pl.BlockSpec((rows, contraction), lambda j, v, gi, ti, *_: (ti[v], 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((rows, out_tile), lambda j, v, gi, ti, *_: (ti[v], j)),
            scratch_shapes=[pltpu.VMEM((2, *matrix_block), dtype), pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((m, out_dim), dtype),
        interpret=interpret,
        name="gmm_dlhs" if transpose_rhs else "gmm_fwd",
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )


def _gmm(lhs, rhs, group_sizes, rows: int, out_tile: int, transpose_rhs: bool, interpret: bool,
         short: bool = False):
    call = _gmm_call(*lhs.shape, rhs.shape[0], rhs.shape[1 if transpose_rhs else 2], lhs.dtype,
                     rows, out_tile, transpose_rhs, interpret)
    visits = _visits(group_sizes, lhs.shape[0], rows, False, short)
    return call(*visits, *_matrix_slots(group_sizes), lhs, rhs)


# --------------------------------------------------------------------------- drhs
def _drhs_kernel(group_ids, tile_ids, starts, ends, num, lhs_ref, dout_ref, out_ref, acc, *,
                 rows: int):
    v = pl.program_id(2)
    group = group_ids[v]
    new_group = (v == 0) | (group_ids[jnp.maximum(v - 1, 0)] != group)

    # `out_ref` is the block of the visit before (`_drhs`): that group is complete
    # where this visit starts another, or is the first past the last real one.
    @pl.when((v > 0) & (new_group | (v == num[0])))
    def _():
        out_ref[0] = acc[...].astype(out_ref.dtype)

    @pl.when(v < num[0])
    def _():
        @pl.when(new_group)
        def _():
            acc[...] = jnp.zeros_like(acc)

        def contract(window, mine):
            lhs = lhs_ref[window, :]
            acc[...] += jax.lax.dot_general(
                jnp.where(mine, lhs, jnp.zeros_like(lhs)), dout_ref[window, :],
                (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

        _for_live_window(tile_ids[v], starts[group], ends[group], rows, DRHS_SUB_ROWS, contract)


@functools.lru_cache(maxsize=None)
def _drhs_call(m: int, k: int, n: int, g: int, dtype, rows: int, tile_k: int, tile_n: int,
               interpret: bool):
    """As `_gmm_call`: one `pallas_call` object for these shapes."""
    return pl.pallas_call(
        functools.partial(_drhs_kernel, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(k // tile_k, n // tile_n, m // rows + g),
            in_specs=[
                pl.BlockSpec((rows, tile_k), lambda i, j, v, gi, ti, *_: (ti[v], i)),
                pl.BlockSpec((rows, tile_n), lambda i, j, v, gi, ti, *_: (ti[v], j)),
            ],
            # One visit late: a group's block is written back after the visit that follows its last.
            out_specs=pl.BlockSpec((1, tile_k, tile_n),
                                   lambda i, j, v, gi, ti, *_: (gi[jnp.maximum(v - 1, 0)], i, j)),
            scratch_shapes=[pltpu.VMEM((tile_k, tile_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((g, k, n), dtype),
        interpret=interpret,
        name="gmm_drhs",
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )


def _drhs(lhs, dout, group_sizes, g: int, rows: int, tile_k: int, tile_n: int, interpret: bool):
    call = _drhs_call(*lhs.shape, dout.shape[1], g, lhs.dtype, rows, tile_k, tile_n, interpret)
    return call(*_visits(group_sizes, lhs.shape[0], rows, True), lhs, dout)


# --------------------------------------------------------------------- the product
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _pallas_grouped_matmul(lhs, rhs, group_sizes, interpret: bool, short: bool = False):
    tiles = _tiles(lhs.shape[0], rhs.shape[1], rhs.shape[2], lhs.dtype.itemsize)
    return _gmm(lhs, rhs, group_sizes, tiles.rows, tiles.out_fwd, False, interpret, short)


def _fwd_rule(lhs, rhs, group_sizes, interpret, short):
    return _pallas_grouped_matmul(lhs, rhs, group_sizes, interpret, short), (lhs, rhs, group_sizes)


def _bwd_rule(interpret, short, res, dout):
    lhs, rhs, group_sizes = res
    tiles = _tiles(lhs.shape[0], rhs.shape[1], rhs.shape[2], lhs.dtype.itemsize)
    dout = dout.astype(lhs.dtype)
    dlhs = _gmm(dout, rhs, group_sizes, tiles.rows, tiles.out_dlhs, True, interpret, short)
    drhs = _drhs(lhs, dout, group_sizes, rhs.shape[0], tiles.drhs_rows, tiles.drhs_k,
                 tiles.drhs_n, interpret)
    return dlhs, drhs.astype(rhs.dtype), None


_pallas_grouped_matmul.defvjp(_fwd_rule, _bwd_rule)


def xla_grouped_matmul(lhs, rhs, group_sizes):
    """The XLA form: what runs off the TPU, and the kernels' test reference."""
    return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32))


def grouped_matmul(lhs, rhs, group_sizes, backend: Optional[str] = None, interpret: bool = False,
                   short: bool = False):
    """`out[i] = lhs[i] @ rhs[group of row i]` for `lhs` (m, k) whose rows lie
    in `g` consecutive groups of `group_sizes` rows, `rhs` (g, k, n).
    `group_sizes` must sum to m, or with `short` to m at most: the rows past
    the last group are multiplied by nothing and add nothing to the gradient
    of `rhs`; their rows of the result and of the gradient of `lhs` are zeros
    in the XLA form and unwritten by the kernels: not to be read.
    Differentiable in `lhs` and `rhs`.

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
    pallas = functools.partial(_pallas_grouped_matmul, interpret=interpret, short=short)
    if backend == "pallas":
        return pallas(lhs, rhs, group_sizes)
    return jax.lax.platform_dependent(lhs, rhs, group_sizes, tpu=pallas, default=xla_grouped_matmul)
