"""The two row movers of a mixture-of-experts layer, between token order and
rows that lie sorted by expert: `sum_rows`, the sum of each token's rows, and
`gather_rows`, its transpose, each token's row put where its pairs sorted.

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
  at a time, the next chunk (the next block's first, at a block's end) in
  flight while this one is summed. How many rows a chunk has follows the
  share of the pairs that the rows hold (`chunk_rows`): as many as a slot
  takes where every pair is owned, and a block's last chunk is then filled up
  with copies of tile 0 that nobody owns, so that a chunk's copies are all
  alike; twice a block's owned rows where the rows are the prefix of a layer
  that holds some of the experts, and then a block's own pieces are copied
  and no other (what else the stage holds goes into the product as zeros).
- A staged row is put on its token by a product with a 0/1 matrix, `P[r, t] =
  (one of inverse[t, 0:k] is the sorted position of staged row r)`, exact in
  any dtype, accumulated in float32 in VMEM. The rows of a piece that are not
  the block's own (another block's, read because copies are `PIECE` rows) go
  into the product as zeros: they add nothing, whatever they hold.

`gather_rows(x, order, inverse, runs, k)`: row `order[i] // k` of `x` for the
first rows of the sort, where they hold every owned pair (the prefix form of
`models/moe.py`). XLA's gather brings the prefix's rows in one at a time, the
half of them that nobody owns too (1.14 ms for 32,768 rows out of 32,768
tokens at LFM2's shapes, 120 GB/s written: PERF.md section 6, PR 40). The
kernel is `sum_rows` the other way round, over the same `runs`:

- One program per block of tokens, whose `BLOCK` rows of `x` are a pipelined
  operand. The staged tiles are the product of the same 0/1 matrix with the
  block's tokens (a row is one row times 1.0 and exact zeros), a chunk at a
  time, and each tile whose 8 rows are all one expert's and all known goes to
  HBM by one copy, in flight while the next chunk is made.
- A run seldom ends on a multiple of `PIECE`, and a tile is written whole. The
  grid is sequential, so the open tile of each expert rides a VMEM carry: a
  run's last piece, if it does not fill its tile, is kept there and not
  written, and the first piece of that expert's next run (a later block's)
  takes the rows before its own out of the carry, by a second 0/1 product.
  So every such tile is written once, by the block that completes it.
- A tile that a group ends inside holds rows of the next group too, which
  earlier blocks own: a group's first tile, where it is such a tile, is kept
  in the carry's other half and not written either. The last program makes
  every one of those tiles (one a group at most) out of both halves by one
  more 0/1 product (`_tile_owners`: which kept row each of its rows is) and
  writes them, then zeros from the last owned row to the next multiple of
  `ZEROED`: what lies behind is never written, and nobody may read it.
- Every wait is for copies started in the same program or, at a slot's reuse,
  in the two chunks before; the counts ride SMEM.

`rows_read` and `rows_written` count the rows the two kernels' copies move for
a concrete routing.
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
CHUNK_ROWS = (512, 256, 128)  # rows in a slot: `chunk_rows` picks
ZEROED = 512  # `gather_rows` writes zeros behind the owned rows up to a multiple of this: a kernel's longest row tile
UNROLL = 16  # pieces to a turn of the kernel's loops over a chunk's pieces


class Runs(NamedTuple):
    """What `sorted_runs` hands the kernels, by block of `BLOCK` tokens: how many
    pieces cover the block's runs, and for each (`max_pieces` a block, flat) the
    `PIECE`-row tile of the sorted rows it reads and the rows [lo, hi) of it that
    are the block's own. Entries past a block's count are 0. `sizes`: the pairs
    of every expert, which say where its group lies (`gather_rows` reads them)."""
    count: jax.Array  # (blocks,)
    tile: jax.Array  # (blocks * max_pieces,)
    lo: jax.Array
    hi: jax.Array
    sizes: jax.Array  # (experts,)


def _max_pieces(k: int, n_experts: int) -> int:
    """The most pieces a block's runs take, in whole chunks of the largest
    size (every size divides it; both forms of a layer that holds some of the
    experts, `models/moe.py`, read one list at two sizes): a run of L rows
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
    return Runs(count, tile.reshape(-1), lo.reshape(-1), hi.reshape(-1), sizes)


def _tile_owners(runs: Runs):
    """What `gather_rows` needs to write tiles that several writers own rows
    of (its place in the module's docstring), from `runs`: for every piece its
    expert, times two, plus one where the piece's tile also holds rows of a
    group before that expert's; `edges`, for every expert the tile its group
    ends inside (-1 where it ends on a tile's edge or is empty), then the rows
    that have an owner; `edge_src` (`carry_rows`, 1), for the rows of those
    tiles the row of the kernel's carry each is kept in (-1: nobody's). A few
    compares against the experts' bounds, made where the kernel is called and
    not beside `sorted_runs`: what the layer keeps for its backward pass stays
    what it was (with these among it XLA made three layers' routers, sorts and
    short convolutions again in the LFM2 step, 21 ms: PERF.md section 6, PR 40)."""
    n_experts = runs.sizes.shape[0]
    experts = jnp.arange(n_experts, dtype=jnp.int32)
    group_end = jnp.cumsum(runs.sizes)
    group_start = group_end - runs.sizes

    def start_of(group):  # of each group in `group` (..., 1); 0 past the last
        return jnp.sum(jnp.where(group == experts, group_start, 0), axis=-1)

    first_own = (runs.tile * PIECE + runs.lo)[:, None]
    expert = jnp.minimum(jnp.sum(group_end <= first_own, axis=-1, dtype=jnp.int32), n_experts - 1)
    what = 2 * expert + (runs.tile * PIECE < start_of(expert[:, None]))
    at = group_end // PIECE  # the tile a group ends inside, and its rows
    inside = (runs.sizes > 0) & (group_end % PIECE > 0)
    row = jnp.arange(PIECE, dtype=jnp.int32)
    position = at[:, None] * PIECE + row
    group = jnp.sum(group_end <= position[..., None], axis=-1, dtype=jnp.int32)  # `n_experts`: nobody's
    half = carry_rows(n_experts)
    kept = jnp.where(at[:, None] * PIECE < start_of(group[..., None]), half, 0) + group * PIECE + row
    kept = jnp.where((group < n_experts) & inside[:, None], kept, -1).reshape(-1, 1)
    edge_src = jnp.pad(kept, ((0, half - kept.shape[0]), (0, 0)), constant_values=-1)
    edges = jnp.concatenate([jnp.where(inside, at, -1), group_end[-1:]]).astype(jnp.int32)
    return what, edges, edge_src


def _tiles_by_block(experts, n_held: Optional[int]):
    """For concrete `experts` (tokens, k): by block of tokens the (first, last)
    tile of each of its runs, and the pairs that have an owner; with `n_held`
    the pairs of experts from there on are nobody's (sorted behind the others)."""
    experts = np.asarray(experts)
    tokens, k = experts.shape
    flat = experts.reshape(-1) if n_held is None else np.minimum(experts.reshape(-1), n_held)
    position = np.empty(tokens * k, np.int64)
    position[np.argsort(flat, kind="stable")] = np.arange(tokens * k)
    blocks = []
    for b in range(tokens // BLOCK):
        pairs = slice(b * BLOCK * k, (b + 1) * BLOCK * k)
        runs = [position[pairs][flat[pairs] == e] for e in np.unique(flat[pairs]) if n_held is None or e < n_held]
        blocks.append([(run.min() // PIECE, run.max() // PIECE) for run in runs])
    return blocks, tokens * k if n_held is None else int((flat < n_held).sum())


def rows_read(experts, chunk_rows: int = CHUNK_ROWS[0], n_held: Optional[int] = None) -> int:
    """The rows `sum_rows`' copies move for concrete `experts` (tokens, k),
    where `tokens * k` are needed (with `n_held`: the pairs of experts under
    it, the others lying behind a prefix that is never read): each (block of
    tokens, expert) run pays for the `PIECE`-row tiles that hold a row of it
    (a block that owns nothing, for one), and at the largest `chunk_rows` each
    block for whole chunks (what its last chunk lacks is read from tile 0); at
    a smaller one the kernel copies a block's own pieces alone."""
    blocks, _ = _tiles_by_block(experts, n_held)
    rows = 0
    for runs in blocks:
        tiles = max(sum(int(last - first) + 1 for first, last in runs), 0 if n_held is None else 1)
        rows += -(-tiles * PIECE // chunk_rows) * chunk_rows if chunk_rows == CHUNK_ROWS[0] else tiles * PIECE
    return rows


def rows_written(experts, n_held: Optional[int] = None) -> int:
    """The rows `gather_rows`' copies write for concrete `experts`, where the
    owned pairs are needed: every tile that holds an owned row once (a tile
    that two runs share is written by whoever completes it, or at the end),
    and zeros from the last owned row to the next multiple of `ZEROED`."""
    blocks, owned = _tiles_by_block(experts, n_held)
    tiles = {t for runs in blocks for first, last in runs for t in range(first, last + 1)}
    return len(tiles) * PIECE + (-(-owned // ZEROED) * ZEROED - -(-owned // PIECE) * PIECE)


def chunk_rows(width: int, itemsize: int, k: int, rows: int, pairs: int, times: int = 2) -> Optional[int]:
    """The rows of a chunk, for `rows` sorted rows this wide that hold what is
    owned of `pairs` pairs, `k` a token: of the sizes that fit a slot, the
    smallest that holds `times` what a block of tokens owns if the rows are
    all owned, else the largest. Nothing where none fits. A layer that holds
    every expert (1,024 rows a block at OLMoE's 8 a token) stages 512 rows. The
    prefix form of one that holds 8 of 64 at 4 a token owns 128 rows a block at
    the bound and 64 at an even routing, in runs a tile long that lie in two
    tiles each: `sum_rows` takes twice the 128 (at 512 its blocks copied,
    masked and multiplied 64 pieces of which 46 were tile 0: 1,165 us a call,
    751 at 256, 618 with the copies past a block's last piece left out; at 128
    572, but 717 for 648 where the held experts get 1.4 times their share) and
    `gather_rows`, whose cost a chunk goes by the chunk's rows, the 128
    itself (627 us for 797 at 256; PERF.md section 6, PR 40)."""
    fit = [r for r in CHUNK_ROWS if r * width * itemsize <= STAGE_BYTES]
    enough = [r for r in fit if r * pairs >= times * BLOCK * k * rows]
    return min(enough) if enough else max(fit, default=None)


def _tiled(rows, runs: Optional[Runs]) -> bool:
    """Whether a kernel can run: whole blocks of tokens, a width of whole
    lane tiles, a 16- or 32-bit dtype, a stage slot that holds a chunk and is
    no smaller than the block's float32 sums."""
    width, itemsize = rows.shape[1], rows.dtype.itemsize
    return (runs is not None and width % 128 == 0 and itemsize in (2, 4)
            and CHUNK_ROWS[-1] * width * itemsize <= STAGE_BYTES and BLOCK * width * 4 <= STAGE_BYTES)


def _sum_rows_kernel(count, tile, lo, hi, inverse_ref, rows_hbm, out_ref, stage, acc, sem, first_slot,
                     *, chunk: int, max_pieces: int, k: int, whole: bool):
    b = pl.program_id(0)
    n_chunks = (count[b] + chunk - 1) // chunk

    # The slots alternate through the whole call, so that a block can start the next
    # block's first chunk: `first_slot` carries where this block's first one went.
    @pl.when(b == 0)
    def _():
        first_slot[0] = 0

    first = first_slot[0]

    def for_pieces(block, c, body):
        """`body(i)` for every piece i of chunk `c` of `block`. `whole`: `chunk` of
        them whatever the block owns, `UNROLL` to a turn of a loop: the kernel's
        text, which every process traces and every lowering of a step lowers again,
        stays short. With each copy written out a call took 654 us for 663, and a
        warm set-up of the OLMoE cell 3.2 s more (PERF.md section 6, PR 34). Else
        the block's own pieces alone, however many (PR 40)."""
        def some(turn, carry):
            for u in range(UNROLL):
                body(turn * UNROLL + u)
            return carry

        if whole:
            jax.lax.fori_loop(0, chunk // UNROLL, some, None)
        else:
            jax.lax.fori_loop(0, jnp.minimum(count[block] - c * chunk, chunk),
                              lambda i, carry: (body(i), carry)[1], None)

    def copy(block, c, slot, i):
        """Piece i of chunk `c` of `block` into `slot`: with `whole` also past the block's
        last piece (tile 0 then, owned by nobody), so that a chunk's copies are all alike."""
        at = pl.multiple_of(tile[block * max_pieces + c * chunk + i] * PIECE, PIECE)
        to = pl.ds(pl.multiple_of(i * PIECE, PIECE), PIECE)
        return pltpu.make_async_copy(rows_hbm.at[pl.ds(at, PIECE), :], stage.at[slot, to, :], sem.at[slot])

    @pl.when(b == 0)
    def _():
        for_pieces(b, 0, lambda i: copy(b, 0, first, i).start())

    acc[...] = jnp.zeros_like(acc)
    row = jax.lax.broadcasted_iota(jnp.int32, (PIECE, 1), 0)

    def add_chunk(c, carry):
        slot = (first + c) % 2
        # The next chunk, in flight while this one is summed: this block's, or the next block's first.
        last = c + 1 == n_chunks

        @pl.when(jnp.logical_not(last) | (b + 1 < pl.num_programs(0)))
        def _():
            block, chunk_of_it = jnp.where(last, b + 1, b), jnp.where(last, 0, c + 1)
            for_pieces(block, chunk_of_it, lambda i: copy(block, chunk_of_it, 1 - slot, i).start())

        for_pieces(b, c, lambda i: copy(b, c, slot, i).wait())
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
def _sum_rows_call(tokens: int, width: int, dtype, k: int, max_pieces: int, chunk: int, interpret):
    """The `pallas_call` for these shapes and `chunk` pieces a chunk, made once
    a process (as `_gmm_call`): how many sorted rows there are to read from is
    among them only through the chunk."""
    blocks = tokens // BLOCK
    return pl.pallas_call(
        functools.partial(_sum_rows_kernel, chunk=chunk, max_pieces=max_pieces, k=k,
                          whole=chunk * PIECE == CHUNK_ROWS[0]),
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
    chunk = chunk_rows(rows.shape[1], rows.dtype.itemsize, k, rows.shape[0], inverse.shape[0]) // PIECE
    call = _sum_rows_call(inverse.shape[0] // k, rows.shape[1], rows.dtype, k, max_pieces, chunk, interpret)
    # (k, tokens): dense in HBM, where (tokens, k) would be padded to 128 lanes.
    return call(*runs[:4], inverse.reshape(-1, k).T.astype(jnp.int32), rows)


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


# --------------------------------------------------------------------------- gather_rows
def carry_rows(n_experts: int) -> int:
    """The rows of one half of `gather_rows`' carry (a tile an expert): whole lane tiles, since
    a 0/1 matrix that picks rows of the carry has them along its lanes."""
    return -(-n_experts * PIECE // 128) * 128


def _gather_rows_kernel(count, tile, span, edges, inverse_ref, edge_src_ref, x_ref, out_hbm,
                        stage, held, edge, sem, kept_sem, state,
                        *, chunk: int, max_pieces: int, k: int, n_experts: int):
    b = pl.program_id(0)
    n_chunks = (count[b] + chunk - 1) // chunk
    # `held`: the block's tokens, then the carry: every expert's open last tile, then every
    # expert's first tile where it is one that the group before ends inside.
    half = (held.shape[0] - BLOCK) // 2
    hp = jax.lax.Precision.HIGHEST if stage.dtype == jnp.float32 else None

    def pick(picks, rows):  # rows chosen by a 0/1 matrix: one row times 1.0 and zeros, exact in any dtype
        return jax.lax.dot_general(jnp.where(picks, 1.0, 0.0).astype(stage.dtype), rows, (((1,), (0,)), ((), ())),
                                   precision=hp, preferred_element_type=jnp.float32).astype(stage.dtype)

    # state: the slot of this block's first chunk (the slots alternate through the whole call), the
    # writes in flight from either slot, the copies into the carry in flight.
    @pl.when(b == 0)
    def _():
        for i in range(4):
            state[i] = 0
        held[BLOCK:, :] = jnp.zeros((2 * half, held.shape[1]), held.dtype)

    first = state[0]

    def from_stage(slot, i):
        return stage.at[slot, pl.ds(pl.multiple_of(i * PIECE, PIECE), PIECE), :]

    def write(slot, i, t):  # piece i of the stage to tile t of the result
        return pltpu.make_async_copy(
            from_stage(slot, i), out_hbm.at[pl.ds(pl.multiple_of(t * PIECE, PIECE), PIECE), :], sem.at[slot])

    def keep(slot, i, at):  # piece i of the stage into the carry
        return pltpu.make_async_copy(
            from_stage(slot, i), held.at[pl.ds(pl.multiple_of(BLOCK + at, PIECE), PIECE), :], kept_sem.at[0])

    def wait(n, copy):  # every copy is `PIECE` rows: any of a semaphore's stands for each of them
        jax.lax.fori_loop(0, n, lambda _, c: (copy.wait(), c)[1], None)

    row = jax.lax.broadcasted_iota(jnp.int32, (PIECE, 1), 0)

    def fields(q):
        s = span[q]
        return s >> 9, (s >> 8) & 1, (s >> 4) & 15, s & 15  # expert, a group's first tile shared, lo, hi

    held[:BLOCK, :] = x_ref[...]

    def put_chunk(c, carried):
        slot = (first + c) % 2
        base = b * max_pieces + c * chunk
        # Down the staged rows: the sorted position of each that is the block's own, and for the
        # rows before them in a run's first tile, which earlier blocks own, where the carry has them.
        position, before = [], []
        for i in range(chunk):
            expert, _, lo, hi = fields(base + i)
            position.append(jnp.where((row >= lo) & (row < hi), tile[base + i] * PIECE + row, -1))
            before.append(jnp.where(row < lo, expert * PIECE + row, -1))
        position, before = jnp.concatenate(position, axis=0), jnp.concatenate(before, axis=0)
        on_token = position == inverse_ref[0:1, :]
        for j in range(1, k):  # a sorted position is one pair's: at most one of a token's k is a staged row's
            on_token |= position == inverse_ref[j:j + 1, :]
        in_carry = before == jax.lax.broadcasted_iota(jnp.int32, (1, half), 1)
        wait(state[1 + slot], write(slot, 0, 0))  # what the chunk before the last wrote from this slot
        wait(state[3], keep(slot, 0, 0))  # the copies of the chunk before read the other slot; the carry is whole
        # One product over the tokens and the carry: each staged row is one of them, or zeros.
        stage[slot] = pick(jnp.concatenate([on_token, in_carry], axis=1), held[:BLOCK + half, :])

        def piece(i, started):
            writes, keeps = started
            expert, shared, lo, hi = fields(base + i)
            owns = hi > lo
            whole = owns & (hi == PIECE) & (shared == 0)
            open_ = owns & (hi < PIECE)
            shared = owns & (shared == 1)

            @pl.when(whole)
            def _():
                write(slot, i, tile[base + i]).start()

            @pl.when(open_)
            def _():
                keep(slot, i, expert * PIECE).start()

            @pl.when(shared)
            def _():
                keep(slot, i, half + expert * PIECE).start()

            return (writes + whole.astype(jnp.int32),
                    keeps + shared.astype(jnp.int32) + open_.astype(jnp.int32))

        here = jnp.minimum(count[b] - c * chunk, chunk)
        state[1 + slot], state[3] = jax.lax.fori_loop(0, here, piece, (jnp.int32(0), jnp.int32(0)))
        return carried

    jax.lax.fori_loop(0, n_chunks, put_chunk, None)
    state[0] = (first + n_chunks) % 2

    # The tiles that a group ends inside, made of what the carry holds of each group; zeros
    # from the last owned row to the next multiple of `ZEROED`.
    @pl.when(b + 1 == pl.num_programs(0))
    def _():
        wait(state[1], write(0, 0, 0))
        wait(state[2], write(1, 0, 0))
        wait(state[3], keep(0, 0, 0))
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 2 * half), 1)
        edge[:half, :] = pick(edge_src_ref[...] == lane, held[BLOCK:, :])
        edge[half:, :] = jnp.zeros((PIECE, edge.shape[1]), edge.dtype)

        def to(i, t):
            return pltpu.make_async_copy(
                edge.at[pl.ds(pl.multiple_of(i * PIECE, PIECE), PIECE), :],
                out_hbm.at[pl.ds(pl.multiple_of(t * PIECE, PIECE), PIECE), :], sem.at[0])

        def an_edge(e, n):
            @pl.when(edges[e] >= 0)
            def _():
                to(e, edges[e]).start()
            return n + (edges[e] >= 0).astype(jnp.int32)

        owned = edges[n_experts]
        zeros_from = (owned + PIECE - 1) // PIECE
        zeros_to = jnp.minimum((owned + ZEROED - 1) // ZEROED * ZEROED, out_hbm.shape[0]) // PIECE

        def zeros(t, carried):
            to(half // PIECE, t).start()
            return carried

        n = jax.lax.fori_loop(0, n_experts, an_edge, jnp.int32(0))
        jax.lax.fori_loop(zeros_from, zeros_to, zeros, None)
        wait(n + jnp.maximum(zeros_to - zeros_from, 0), to(0, 0))


@functools.lru_cache(maxsize=None)
def _gather_rows_call(tokens: int, width: int, dtype, k: int, n: int, max_pieces: int, chunk: int,
                      n_experts: int, interpret):
    """The `pallas_call` that writes `n` sorted rows, made once a process."""
    half = carry_rows(n_experts)
    return pl.pallas_call(
        functools.partial(_gather_rows_kernel, chunk=chunk, max_pieces=max_pieces, k=k, n_experts=n_experts),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(tokens // BLOCK,),
            in_specs=[pl.BlockSpec((k, BLOCK), lambda b, *_: (0, b)),
                      pl.BlockSpec((half, 1), lambda b, *_: (0, 0)),
                      pl.BlockSpec((BLOCK, width), lambda b, *_: (b, 0))],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((2, chunk * PIECE, width), dtype),
                            pltpu.VMEM((BLOCK + 2 * half, width), dtype),
                            pltpu.VMEM((half + PIECE, width), dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA((1,)),
                            pltpu.SMEM((4,), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n, width), dtype),
        interpret=interpret,
        name="gather_rows",
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )


# A function of its own in the step's program, as `_pallas_sum_rows`.
@functools.partial(jax.jit, static_argnames=("k", "n", "interpret"))
def _pallas_gather_rows(x, inverse, runs: Runs, k: int, n: int, interpret=False):
    max_pieces = runs.tile.shape[0] // runs.count.shape[0]
    chunk = chunk_rows(x.shape[1], x.dtype.itemsize, k, n, inverse.shape[0], times=1) // PIECE
    call = _gather_rows_call(x.shape[0], x.shape[1], x.dtype, k, n, max_pieces, chunk,
                             runs.sizes.shape[0], interpret)
    what, edges, edge_src = _tile_owners(runs)
    span = what * 256 + runs.lo * 16 + runs.hi  # one scalar a piece
    return call(runs.count, runs.tile, span, edges, inverse.reshape(-1, k).T.astype(jnp.int32), edge_src, x)


def gather_rows(x, order, inverse, runs: Optional[Runs], k: int, backend: Optional[str] = None,
                interpret=False):
    """Row `order[i] // k` of `x` (tokens, width) for every i that has an owner:
    `order` the first rows of the sort of the `tokens * k` pairs by expert,
    which hold every owned pair; `inverse` (tokens * k,) and `runs` as for
    `sum_rows`, whose transpose this is. The XLA form is `x[order // k]`, a
    row for every i. The kernel writes the owned rows and zeros up to the next
    multiple of `ZEROED`; what lies behind is whatever the buffer held, and a
    caller must not read it (as the grouped matmuls leave their rows there,
    `ops/grouped_matmul.py`).

    backend: "pallas" | "xla" | None: as `sum_rows`."""
    tiled = _tiled(x, runs) and order.shape[0] % PIECE == 0
    if backend == "pallas" and not tiled:
        raise ValueError(f"gather_rows(backend='pallas'): {x.shape} by {k} does not tile: "
                         f"tokens must be a multiple of {BLOCK}, the width of 128")
    if backend == "xla" or not tiled:
        return x[order // k]
    pallas = functools.partial(_pallas_gather_rows, k=k, n=order.shape[0], interpret=interpret)
    if backend == "pallas":
        return pallas(x, inverse, runs)
    return jax.lax.platform_dependent(x, order, inverse, runs,
                                      tpu=lambda x, order, inverse, runs: pallas(x, inverse, runs),
                                      default=lambda x, order, inverse, runs: x[order // k])
