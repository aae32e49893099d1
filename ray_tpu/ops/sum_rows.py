"""The sum of each token's rows, out of rows that lie sorted by expert.

`sum_rows(rows, inverse, runs, k)`: `rows` (width wide) are the results of a
mixture-of-experts layer in expert order, the sorted rows or the prefix of
them that holds every row `inverse` is asked for; `inverse[t * k + j]` is
where the j-th pair of token t went (`models/moe.py expert_order`), and
`out[t] = sum over j of rows[inverse[t * k + j]]`, added up in float32 and
rounded once (a pair sorted behind a prefix adds nothing: an expert held on
another chip). Off the TPU that is a gather and a reduction (`xla_sum_rows`);
XLA's gather on the TPU reads every 4 KB row by itself and writes them all in
token order only for the reduction to read them again (2.6 ms for 268 MB at
OLMoE's shapes: PERF.md section 6, PR 34). On the TPU one Pallas kernel,
`sum_rows`, reads the rows in the runs the sort left them in, and writes only
the sums:

- The sort is stable, so the rows that a block of consecutive tokens owns in
  one expert's group are one contiguous run. `sorted_runs` (a few XLA
  operations on the routing, made once beside `expert_order`) lists, for each
  block of `BLOCK` tokens, the `PIECE`-row aligned pieces of the sorted array
  that cover its runs, and which rows of each piece are the block's own.
- One program per block copies its pieces from HBM into a VMEM stage, a chunk
  of `CHUNK_ROWS` rows at a time, the next chunk (the next block's first, at
  a block's end) in flight while this one is summed. A block's last chunk is
  filled up with copies of tile 0 that nobody owns.
- A staged row is put on its token by a product with a 0/1 matrix, `P[r, t] =
  (one of inverse[t, 0:k] is the sorted position of staged row r)`, exact in
  any dtype, accumulated in float32 in VMEM. The rows of a piece that are not
  the block's own (another block's, read because copies are `PIECE` rows) go
  into the product as zeros: they add nothing, whatever they hold.

`rows_read` counts the rows the copies move for a concrete routing.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# What the chip said at OLMoE's shapes, us a call in an earlier form of the kernel (PERF.md section
# 6, PR 34): 16-row pieces and blocks of 256 tokens 919, of 128 tokens 859; 8-row pieces 874 and
# 745; chunks of 256 rows 790. Smaller blocks read more rows twice, larger ones multiply more zeros.
PIECE = 8  # rows a copy moves: a tile of the sorted rows in HBM, (8, 128) for 16- and 32-bit arrays
BLOCK = 128  # tokens a program sums
STAGE_BYTES = 2 * 1024 * 1024  # one of the two slots of the stage
CHUNK_ROWS = (512, 256, 128)  # rows in a slot: as many as fit STAGE_BYTES
UNROLL = 16  # pieces to a turn of the kernel's loops over a chunk's pieces


class Runs(NamedTuple):
    """What `sorted_runs` hands the kernel, by block of `BLOCK` tokens: how many
    pieces cover the block's runs, and for each (`max_pieces` a block, flat) the
    `PIECE`-row tile of the sorted rows it reads and the rows [lo, hi) of it that
    are the block's own. Entries past a block's count are 0."""
    count: jax.Array  # (blocks,)
    tile: jax.Array  # (blocks * max_pieces,)
    lo: jax.Array
    hi: jax.Array


def _max_pieces(k: int, n_experts: int) -> int:
    """The most pieces a block's runs take, in whole chunks: a run of L rows
    touches at most (L - 1) // PIECE + 2 tiles."""
    bound = BLOCK * k // PIECE + 2 * min(n_experts, BLOCK * k)
    most = CHUNK_ROWS[0] // PIECE
    return -(-bound // most) * most


# `inline=True`: as `grouped_matmul._visits`.
@functools.partial(jax.jit, static_argnums=(1, 2), inline=True)
def sorted_runs(experts, n_experts: int, some_unowned: bool = False) -> Optional[Runs]:
    """The pieces of the sorted rows that each block of `BLOCK` tokens owns,
    for `experts` (tokens, k), each token's choices: nothing where the tokens
    do not come in whole blocks (`sum_rows` takes the XLA form then).

    With `some_unowned`, a choice of `n_experts` or more is nobody's (an
    expert held on another chip, `models/moe.py`): it has no run, the kernel
    never reads its row, and it adds nothing to its token's sum. A block may
    then own no piece at all; it is given one that holds none of its rows, so
    that every block has a chunk for the block before it to start."""
    tokens, k = experts.shape
    if tokens % BLOCK:
        return None
    blocks, max_pieces = tokens // BLOCK, _max_pieces(k, n_experts)
    of_block = experts.reshape(blocks, BLOCK * k, 1).astype(jnp.int32)
    counts = jnp.sum(of_block == jnp.arange(n_experts, dtype=jnp.int32), axis=1, dtype=jnp.int32)
    sizes = counts.sum(axis=0)
    # A block's run in expert e starts after the groups before e and e's pairs of the blocks before.
    starts = jnp.cumsum(sizes) - sizes + jnp.cumsum(counts, axis=0) - counts  # (blocks, E)
    ends = starts + counts
    first = starts // PIECE
    n = jnp.where(counts > 0, (ends - 1) // PIECE - first + 1, 0)
    upto = jnp.cumsum(n, axis=1)
    q = jnp.arange(max_pieces, dtype=jnp.int32)
    # Piece q of a block belongs to the run that the running count of pieces puts it in.
    run = jnp.sum(upto[:, None, :] <= q[None, :, None], axis=-1)  # (blocks, max_pieces); E past the last
    mine = run[..., None] == jnp.arange(n_experts, dtype=jnp.int32)

    def of_run(a):  # (blocks, E) -> (blocks, max_pieces), 0 past the block's last piece
        return jnp.sum(jnp.where(mine, a[:, None, :], 0), axis=-1)

    tile = of_run(first - (upto - n)) + jnp.where(run < n_experts, q, 0)
    lo = jnp.clip(of_run(starts) - tile * PIECE, 0, PIECE)
    hi = jnp.clip(of_run(ends) - tile * PIECE, 0, PIECE)
    count = jnp.maximum(upto[:, -1], 1) if some_unowned else upto[:, -1]
    return Runs(count, tile.reshape(-1), lo.reshape(-1), hi.reshape(-1))


def rows_read(experts, chunk_rows: int = CHUNK_ROWS[0]) -> int:
    """The rows the kernel's copies move for concrete `experts` (tokens, k),
    where `tokens * k` are needed: each (block of tokens, expert) run pays for
    the `PIECE`-row tiles that hold a row of it, and each block for whole
    chunks of `chunk_rows` (what its last chunk lacks is read from tile 0).
    With `chunk_rows=PIECE`: the tiles of the runs alone."""
    experts = np.asarray(experts)
    tokens, k = experts.shape
    flat = experts.reshape(-1)
    position = np.empty(tokens * k, np.int64)
    position[np.argsort(flat, kind="stable")] = np.arange(tokens * k)
    rows = 0
    for b in range(tokens // BLOCK):
        pairs = slice(b * BLOCK * k, (b + 1) * BLOCK * k)
        tiles = 0
        for e in np.unique(flat[pairs]):
            run = position[pairs][flat[pairs] == e]
            tiles += run.max() // PIECE - run.min() // PIECE + 1
        rows += -(-int(tiles) * PIECE // chunk_rows) * chunk_rows
    return rows


def chunk_rows(width: int, itemsize: int) -> Optional[int]:
    """The rows of a chunk for rows this wide: nothing where none fits a slot."""
    return next((r for r in CHUNK_ROWS if r * width * itemsize <= STAGE_BYTES), None)


def _tiled(rows, runs: Optional[Runs]) -> bool:
    """Whether the kernel can run: whole blocks of tokens, a width of whole
    lane tiles, a 16- or 32-bit dtype, a stage slot that holds a chunk and is
    no smaller than the block's float32 sums."""
    width, itemsize = rows.shape[1], rows.dtype.itemsize
    return (runs is not None and width % 128 == 0 and itemsize in (2, 4)
            and chunk_rows(width, itemsize) is not None and BLOCK * width * 4 <= STAGE_BYTES)


def _sum_rows_kernel(count, tile, lo, hi, inverse_ref, rows_hbm, out_ref, stage, acc, sem, first_slot,
                     *, chunk: int, max_pieces: int, k: int):
    b = pl.program_id(0)
    n_chunks = (count[b] + chunk - 1) // chunk

    # The slots alternate through the whole call, so that a block can start the next
    # block's first chunk: `first_slot` carries where this block's first one went.
    @pl.when(b == 0)
    def _():
        first_slot[0] = 0

    first = first_slot[0]

    def for_pieces(body):
        """`body(i)` for every piece i of a chunk, `UNROLL` to a turn of a loop: the
        kernel's text, which every process traces and every lowering of a step lowers
        again, stays short. With each copy written out a call took 654 us for 663,
        and a warm set-up of the OLMoE cell 3.2 s more (PERF.md section 6, PR 34)."""
        def some(turn, carry):
            for u in range(UNROLL):
                body(turn * UNROLL + u)
            return carry

        jax.lax.fori_loop(0, chunk // UNROLL, some, None)

    def copy(block, c, slot, i):
        """Piece i of chunk `c` of `block` into `slot`: also past the block's last
        piece (tile 0 then, owned by nobody), so that a chunk's copies are all alike."""
        at = pl.multiple_of(tile[block * max_pieces + c * chunk + i] * PIECE, PIECE)
        to = pl.ds(pl.multiple_of(i * PIECE, PIECE), PIECE)
        return pltpu.make_async_copy(rows_hbm.at[pl.ds(at, PIECE), :], stage.at[slot, to, :], sem.at[slot])

    @pl.when(b == 0)
    def _():
        for_pieces(lambda i: copy(b, 0, first, i).start())

    acc[...] = jnp.zeros_like(acc)
    row = jax.lax.broadcasted_iota(jnp.int32, (PIECE, 1), 0)

    def add_chunk(c, carry):
        slot = (first + c) % 2
        # The next chunk, in flight while this one is summed: this block's, or the next block's first.
        last = c + 1 == n_chunks

        @pl.when(jnp.logical_not(last) | (b + 1 < pl.num_programs(0)))
        def _():
            block, chunk_of_it = jnp.where(last, b + 1, b), jnp.where(last, 0, c + 1)
            for_pieces(lambda i: copy(block, chunk_of_it, 1 - slot, i).start())

        for_pieces(lambda i: copy(b, c, slot, i).wait())
        # Down the staged rows: the sorted position of each that is the block's own, -1 for the others.
        position = []
        for i in range(chunk):
            q = b * max_pieces + c * chunk + i
            mine = (row >= lo[q]) & (row < hi[q])  # none past the block's last piece
            position.append(jnp.where(mine, tile[q] * PIECE + row, -1))
        position = jnp.concatenate(position, axis=0)  # (staged rows, 1)
        staged = stage[slot]
        staged = jnp.where(position >= 0, staged, jnp.zeros_like(staged))
        # A sorted position is one pair's: at most one of a token's k matches a staged row.
        on_token = jnp.zeros((chunk * PIECE, inverse_ref.shape[1]), jnp.float32)
        for j in range(k):
            on_token = jnp.where(position == inverse_ref[j:j + 1, :], 1.0, on_token)
        acc[...] += jax.lax.dot_general(
            on_token.astype(stage.dtype), staged, (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST if stage.dtype == jnp.float32 else None,
            preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, n_chunks, add_chunk, None)
    first_slot[0] = (first + n_chunks) % 2
    out_ref[...] = acc[...].astype(out_ref.dtype)


@functools.lru_cache(maxsize=None)
def _sum_rows_call(tokens: int, width: int, dtype, k: int, max_pieces: int, interpret):
    """The `pallas_call` for these shapes, made once a process (as `_gmm_call`):
    how many sorted rows there are to read from is not among them."""
    chunk = chunk_rows(width, jnp.dtype(dtype).itemsize) // PIECE  # in pieces
    blocks = tokens // BLOCK
    return pl.pallas_call(
        functools.partial(_sum_rows_kernel, chunk=chunk, max_pieces=max_pieces, k=k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(blocks,),
            in_specs=[pl.BlockSpec((k, BLOCK), lambda b, *_: (0, b)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((BLOCK, width), lambda b, *_: (b, 0)),
            scratch_shapes=[pltpu.VMEM((2, chunk * PIECE, width), dtype),
                            pltpu.VMEM((BLOCK, width), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((tokens, width), dtype),
        interpret=interpret,
        name="sum_rows",
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )


# A function of its own in the step's program, called from both places: a step that is
# lowered (every run lowers it, the reference check two more) lowers the kernel once.
@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def _pallas_sum_rows(rows, inverse, runs: Runs, k: int, interpret=False):
    max_pieces = runs.tile.shape[0] // runs.count.shape[0]
    call = _sum_rows_call(inverse.shape[0] // k, rows.shape[1], rows.dtype, k, max_pieces, interpret)
    # (k, tokens): dense in HBM, where (tokens, k) would be padded to 128 lanes.
    return call(*runs, inverse.reshape(-1, k).T.astype(jnp.int32), rows)


def xla_sum_rows(rows, inverse, k: int):
    """The XLA form: what runs off the TPU, and the kernel's test reference."""
    if rows.shape[0] == inverse.shape[0]:
        by_token = rows[inverse]
    else:  # a prefix of the sorted rows: a position past it adds nothing
        by_token = rows.at[inverse].get(mode="fill", fill_value=0)
    by_token = by_token.reshape(-1, k, rows.shape[-1])
    return by_token.astype(jnp.float32).sum(axis=1).astype(rows.dtype)


def sum_rows(rows, inverse, runs: Optional[Runs], k: int, backend: Optional[str] = None,
             interpret=False):
    """`out[t] = sum over j < k of rows[inverse[t * k + j]]` in float32, rounded
    to `rows.dtype`: `rows` (width wide) the sorted rows, or the prefix of them
    that holds every row `inverse` is asked for (a position past it adds
    nothing: the kernel has no run there, the XLA form fills in zeros);
    `inverse` (tokens * k,) the sorted position of every (token, choice) pair,
    `runs` as `sorted_runs` gives them for the same routing.

    backend: "pallas" | "xla" | None: the kernel where the computation is
    lowered for a TPU and the shapes tile (`BLOCK` tokens, a width of 128s),
    else the XLA form."""
    tiled = _tiled(rows, runs)
    if backend == "pallas" and not tiled:
        raise ValueError(f"sum_rows(backend='pallas'): {rows.shape} by {k} does not tile: "
                         f"tokens must be a multiple of {BLOCK}, the width of 128")
    if backend == "xla" or not tiled:
        return xla_sum_rows(rows, inverse, k)
    pallas = functools.partial(_pallas_sum_rows, k=k, interpret=interpret)
    if backend == "pallas":
        return pallas(rows, inverse, runs)
    return jax.lax.platform_dependent(rows, inverse, runs, tpu=pallas,
                                      default=lambda rows, inverse, runs: xla_sum_rows(rows, inverse, k))
