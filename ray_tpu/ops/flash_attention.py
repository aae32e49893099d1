"""Flash attention for TPU as Pallas kernels (forward + backward), with an XLA
form for non-TPU backends (`select_backend` says which one a call runs).

Design (pallas_guide.md playbook):
 - both kernels walk a schedule of (Q tile, K tile) pairs over the score
   matrix of one (batch, head) (`kernel_plan` says which). Causal: only the
   tiles on or under the diagonal are visited, and only those the diagonal
   crosses pay the mask; the rest of the square is never computed.
 - a short causal schedule is unrolled at trace time inside one program per
   (batch, head) that holds the whole head in VMEM: every slice static, the
   diagonal known to the compiler. Otherwise a program is one outer tile
   (grid axis 1) and walks the inner tiles in `fori_loop`s; one body serves
   both forms.
 - forward: online softmax in f32 over the K tiles of each Q tile; row
   statistics stay (tile_q, 1), the layout of the refs they are stored to.
 - backward: ONE fused kernel computing dk/dv per K tile and accumulating dq
   in a VMEM scratch: s/p are recomputed once per tile pair instead of twice
   (the classic two-kernel split recomputes them in both the dq and dkv
   kernels). O(seq) memory, the point of flash attention. Where a head is too
   large for a program to hold q, do and the row statistics whole (from 2 MiB
   a head in VMEM on: 4096 x 256 in bf16), the same fused kernel runs with a
   program a (Q tile, K tile) pair and every operand streamed (`_bwd_pairs`).
 - forward, where a head is too large for a program to hold K and V (above
   2 MiB a head in VMEM: from 8,192 x 128 in bf16 on), where key/value heads
   are fewer than query heads, or where the call brings a selection: a
   program a (Q tile, K tile) pair (`_fwd_pairs`) under a scalar-prefetched
   schedule, Q tiles in turn and under each its K tiles up to the diagonal,
   of several query heads at once: a key/value head's whole group, part of
   it, or several groups with their key/value heads (`_fwd_pairs_plan`, by
   the VMEM a program holds). k, v and the selection's block are fetched and
   the mask made once for all of them. Inside, a tile lies keys down and
   queries across, so what belongs to a query (maximum, sum, alpha) is a
   lane-dense row and no reduction crosses the lanes; the pair is walked a
   128 x 128 block of scores at a time, which stay in registers from the
   product that makes them to the product that uses them. k and v are never
   repeated, and dk, dv leave the backward kernel (a pair of one query head a
   program, `_bwd_pairs`) a query head each for the caller to sum.
 - a selection (`keep`): which keys each query may attend to, shared by the
   heads of a row, a bit a pair (`pack_keep`: 33.5 MB a row of 16,384). Both
   pair kernels take the pair's (tile_q, 128) block of words as one more
   streamed operand and mask every pair by it (`_keep_tile`: a shift and a
   mask a lane tile), the diagonal's mask beside it where it crosses. Every
   tile pair on or under the diagonal is still visited: a token-level choice
   of 2,048 keys in 16,384 leaves none empty (`dsa.live_tiles_share` 1.0,
   PERF.md section 6, PR 42).
 - a mask by structure (`causal=` a hashable description in place of True:
   `BlockDiffusion`, `SlidingWindow`): a static function of two positions,
   which costs no operand. The pair kernels' schedules ask it of every tile pair whether it
   is empty, whole or crossed (`_tiles_under`) and walk the empty ones not at
   all; a crossed pair's mask is made from iotas inside the kernel
   (`_kept_in_pair`). The whole-head forms know the causal diagonal alone, so
   any other mask runs both passes a pair a program. Block diffusion's row of
   16,384 (two copies of 8,192, blocks of 4) walks 160 of 512 pairs of 512 x
   1,024 where the causal diagonal walks 272 (PERF.md section 6, PR 47); a
   window of 2,048 keys on the same row walks 90, the band and nothing else
   (PR 61).
 - a crossed pair's live span: both pair-streamed schedules name, for every
   pair the mask crosses, the run of 128-key blocks of its K tile outside which
   the mask keeps no score of any query of its Q tile (`_live_span`, asked of
   the mask itself, whichever it is), and the kernels' products, mask and
   exponentials run over that run alone: the forward's loop over key blocks
   takes its bounds from the schedule, the backward slices k, v, dk and dv.
   What is skipped is what the mask masks, so no output changes. The span is
   one run wherever it lies: a pair the diagonal crosses has its live keys at
   the start of its K tile, one that a window's lower edge crosses at the end
   (the span's first block is then not 0, which both kernels read from the
   schedule), and one crossed on both sides, a window narrower than a tile,
   in the middle; the mask made inside the span is the same two compares. At 512 x
   1,024 half of the causal diagonal's crossed pairs and two thirds of block
   diffusion's hold their live scores in half their keys (`keys_<scored>of
   <walked>` beside `tiles_...` in the call's scope: 2,112 of 2,176 blocks and
   1,152 of 1,280); at square tiles every span is the whole tile and the
   kernels are the programs they were (PERF.md section 6, PR 48).
 - matmuls run on the MXU with preferred_element_type=float32; inputs can be
   bfloat16.
 - what is kept for the backward pass is the caller's own arrays: both
   `custom_vjp`s take and return (batch, heads, seq, d) and flatten to the
   kernels' (batch * heads, seq, d) inside their rules, so the `o` a rule
   saves is the array it returns. A layer scan stacks every residual a layer,
   and an `o` saved under a shape of its own beside the one the caller's next
   checkpoint saves was one value stacked twice (640 MiB at five layers of 32
   x 16,384 x 128, the bytes over which XLA's rematerialization made three
   projections a second time: PERF.md section 6, PR 60).

The reference repo has no attention kernels at all (it is a distributed-systems
layer); this file exists because long-context is first-class in the TPU build
(SURVEY.md §5 "long-context... designed fresh").
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tile sizes, from the kernel-alone sweep on the v5e (tools/flash_bench.py;
# PERF.md section 6, PR 26; device time of one call, forward / backward, us):
#   (128, 1024, 64) causal: 512-tiles 333 / 754, 256-tiles 325 / 758,
#     128-tiles 349 / 1194, one 1024-tile with nothing skipped 416 / 946; what
#     this replaced, one masked 1024 x 1024 block: 633 / 1044.
#   (32, 2048, 128) causal: 512-tiles 265 / 572, 256-tiles 243 / 681; the
#     loop form at 1024-tiles 527 / 818.
#   In the loop form a tile is dear: (8, 4096, 128) 1024-tiles 393 / 649,
#     512 371 / 684, 256 630 / 1225. Not causal there is nothing to skip:
#     (128, 1024, 64) 1024 450 / 951, 512 633 / 1228.
# So: a causal schedule that unrolls walks 512-tiles (256-tiles skip more of
# the square and are 0.4 % faster at most, and every unrolled tile is traced
# and lowered again at each lowering of a step), and everything else walks
# the largest tile in the loop form, as it always did.
CAUSAL_TILE = 512
FULL_TILE = 1024
# A schedule is unrolled while its tiles are no larger than CAUSAL_TILE and it
# is at most this many tile pairs (the triangle of an 8 x 8 schedule) covering
# at most this many scores: unrolled code keeps every tile's temporaries on
# the kernel's VMEM stack ...
MAX_UNROLLED_TILES = 36
MAX_UNROLLED_SCORES = 2048 * 2048
# ... and its program holds whole heads (seven operands in the backward pass,
# double-buffered) under Mosaic's default 16 MiB, no `vmem_limit_bytes` asked
# for: 2048 x 128 in bf16 compiles for the v5e, larger heads do not. The loop
# form (a tile a program) compiles every shape it compiled before.
MAX_UNROLLED_HEAD_BYTES = 2048 * 128 * 2
# The loop form's backward program holds four whole-head operands and its dq
# scratch beside its tile: from heads of 4096 x 128 in bf16 on, 1024-tiles no
# longer fit under the 16 MiB (`(2, 16, 4096, 128)` misses by 308 KB in an
# ahead-of-time v5e compile, PR 28; `(1, 8, 4096, 128)` fits), 512-tiles do.
# What counts is the VMEM a head takes, and there its last dimension is padded
# to the 128 lanes: a 4096 x 64 head takes what a 4096 x 128 one does, and
# `(8, 32, 4096, 64)` compiles in this form alone (PR 35).
LANES = 128
LONG_HEAD_SEQ = 4096
LONG_HEAD_BYTES = 4096 * 128 * 2
LONG_HEAD_TILE = 512
# Above that size, and for any head wider than the lanes that is not unrolled,
# the backward program no longer holds a head: it is one (Q tile, K tile) pair
# of 512-tiles (`_bwd_pairs`), every operand the pair's own tile, streamed, and
# a head keeps its f32 dq scratch alone. 4096 x 128 is the one size that runs
# whole on the chip today, and nothing above it is held whole: alone, by the
# smallest `vmem_limit_bytes` that compiles for the v5e (PR 39), the whole-head
# form needs 8.0 MiB at 4096 x 128, 10.0 at 6144 x 128, 12.5 at 8192 x 128,
# 14.5 at 3584 x 256, 15.5 at 4096 x 256 (the pair form 5.0, 6.0, 7.5, 8.5,
# 9.0), and inside a step what XLA fuses into the call's operands comes on
# top: 19.03 MiB at 4096 x 256. What a tile takes grows with the width: 2048 x
# 256 at 1024-tiles asks for 16.75 MiB forward and 21.56 backward, 1024 x 512
# whole at 512-tiles for 17.25.
# The sweep on the v5e (tools/flash_bench.py, PR 39; one call at (40, 4096,
# 256) causal, forward / backward, us; the forward is the loop form at every
# size): 512-tiles 3070 / 5724, 256-tiles 3601 / 8674, 512 x 256 (Q x K) 3582 /
# 7351, 256 x 512 3064 / 6412; 57 % and 61 % of the MXU's peak on the causal
# half (the backward's five products counted as four), where jax's own flash
# and splash kernels take 13,103 / 39,061 and 15,520 / 33,431. The same head
# at 128 wide, (32, 4096, 128) in the loop form: 1482 / 2974.
# The largest head the forward program holds (K and V whole, twice over: 14.0
# MiB alone at 4096 x 256 with 512-tiles):
MAX_HEAD_BYTES = 4096 * 256 * 2
# Above it the forward program holds no head either: a program is one (Q tile, K
# tile) pair with every operand streamed (`_fwd_pairs`, the forward in the
# backward's `_bwd_pairs` form), up to the head whose f32 dq scratch (seq x 128
# lanes x 4 B, 8 MiB at 16,384) the backward program still holds beside its
# tiles. The same two programs take a call with a selection (`keep`) or with
# fewer key/value heads than query heads, whatever its size: the selection's
# tile and the shared key/value head are one more index map there.
MAX_STREAMED_HEAD_BYTES = 16384 * 128 * 2
# The pair forms' K tile at heads no wider than the lanes. The sweep on the v5e
# (tools/flash_bench.py, PR 42; one call at (32, 16384, 128) causal, both
# passes a pair of one head a program, forward / backward, us): 512 x 1024 (Q x
# K) 17,879 / 34,335; 512-tiles 30,825 / 41,677; 256 x 512 37,740 / 50,000; 512
# x 256 57,147 / 57,165; 256-tiles 69,526 / 94,960. A program is short there (a
# pair of 512-tiles is 0.13 GFLOP forward) and pays its start-up every time:
# the longer K tile halves the programs. What compiles ahead of time for the
# v5e under the 16 MiB: these five and 256 x 1024; a Q tile of 1,024 fails in
# `flash_bwd` (its f32 dq scratch alone is 8 MiB), a K tile of 2,048 in
# `flash_bwd` at any Q tile.
# The forward since PR 44 (`_fwd_pairs_kernel`; tools/flash_bench.py --tiles
# plan, one call at 512 x 1024, forward us, the form it replaced / this one): 32
# heads on 4 with a selection of 2,048 keys a query (--kv-heads 4 --keep-topk
# 2048), 8 heads x 512 queries a program: 23,761 / 15,804, where the MXU needs
# 11,860 for the 272 walked pairs; the same without a selection 17,960 / 15,181; 32 equal
# heads, four and their key/value heads a program, 17,879 / 15,796 (one a
# program, every block unrolled: 20,011); (8, 8192, 256) at 512-tiles 2,228 /
# 1,869. By --fwd: 8 x 256 15,068 where 8 x 512 was 14,010. By --part at 8 x
# 512: the two products alone 15,519 (the first 8,005, the second 8,269), the
# softmax alone 9,938, neither 4,661: the MXU binds, the vector work hides
# behind it, and the loop over key blocks is the rest (unrolled: 14,026 whole,
# 13,933 the products, 2,375 neither).
# Since PR 48 both kernels score a crossed pair over its live span of keys alone (`_live_span`; one call at (32 on 4,
# 16384, 128) and 512 x 1024, forward / backward, us, every key of every pair / the spans): with that selection
# (--kv-heads 4 --keep-topk 2048) 15,805 / 34,854 -> 15,415 / 34,218, without one (--kv-heads 4) 15,180 / 33,874 ->
# 14,757 / 33,237, over 272 pairs, 32 crossed, 2,112 of their 2,176 blocks of 128 keys scored. Under a mask by
# structure, block diffusion's (--kv-heads 4 --block-diffusion 4: two copies of 8,192 in blocks of 4): 9,562 / 21,368 ->
# 8,560 / 19,455 over the 160 live pairs of 512, 48 of them crossed, 1,152 of 1,280 blocks scored: 65.2 % and 57.4 % of
# the MXU's peak on the 67.1 M kept pairs a head, the floor `kernels.flash_roofline` reads by. A scored block costs 6.99
# / 15.74 under the diagonal and 7.43 / 16.89 here: the crossed pairs' dearer mask (two compares of shifted iotas where
# the diagonal's is one, in 30 % of the pairs where 12 % were) and a Q tile's shorter runs. The backward's noised
# diagonal by its four live 128 x 128 squares of sixteen (a third form of the pair): 19,253, 1.0 ms of a step of 394: left out.
# Before the spans (PR 47): 512-tiles 8,613 / 22,972 over 288 pairs (the forward 10 % faster, the backward 7.5 % slower:
# the plan's tile stays; every span is whole there); 256 x 1024 9,493 / 23,548 over 320.
PAIRS_TILE_K = 1024
NEG_INF = -1e30
# Where the pair-streamed forward's running maximum starts (`_fwd_pairs_kernel`):
# far above `NEG_INF`, far below any score, so that exp(NEG_INF - m) is 0 by itself.
FLOOR = -1e20
# What a program of that forward may hold of Mosaic's default 16 MiB (`_fwd_pairs_plan`; as
# `lightning_indexer.LOSS_VMEM_BYTES`: inside a step XLA's fused operands come on top).
FWD_PAIRS_VMEM_BYTES = 14 * 2 ** 20
# The block of scores its online softmax steps by, (keys, queries): 32 registers of f32, which stay
# in the register file from the product that makes them to the product that uses them; and the
# blocks a trip of its loop over key blocks may hold (every chain of the program, a head's block of
# queries, over as many key blocks as that allows: 2 for 8 heads x 512 queries, 4 for 4 heads). By
# the compiler's own schedule (cycles a program of 8 heads x 512 x 1,024, ahead of time for the v5e,
# PR 44; the MXU's own 16.4 k): a whole (1,024, 512) tile a step 24.9 k; blocks of (256, 128) 22.0 k,
# (256, 256) 21.5 k, (128, 256) 18.6 k, (128, 128) 18.3 k, (64, 256) 50.4 k; (128, 256) with a
# head's statistics read and written a block 17.3 k, as (128, 128). On the chip, us a call: the
# whole tile 18,846, (128, 128) 14,010, (128, 256) 14,242, every block unrolled. Unrolled, a program
# is 128 blocks that every trace of a step traces and lowers again: + 15 s of a 72 s set-up, and
# what holds the interpreter that long brought the runtime's watchdog down on the worker (PERF.md
# sections 6 and 7, PR 44). In a loop, a trip's first and last blocks have nothing to overlap
# with: 64 blocks a trip 18.1 k cycles and 14,624 us, 32 a trip 19.8 k and 15,804, 16 a trip 23.2 k
# (4 heads: 18,224 us at 16 a trip, 15,796 at 32, 14,563 unrolled); set-up + 3.5 s at 32 a trip.
FWD_STEP_KEYS, FWD_STEP_QUERIES, FWD_BLOCKS_A_TRIP = 128, 256, 32
# A selection (`keep`) is packed a bit a (query, key) pair, shared by the heads of
# a row: word [q, span * 128 + lane] of int32 holds in bit b the key
# `span * 4096 + b * 128 + lane`, so a K tile of 128 * n keys is n bits of one
# (tile_q, 128) block, each a shift and a mask of whole lane tiles away
# (`_keep_tile`): 33.5 MB a row of 16,384 where a byte a pair is 268.
KEEP_BITS = 32
KEEP_SPAN = KEEP_BITS * LANES


def _head_bytes(seq: int, head_dim: int, itemsize: int) -> int:
    """The VMEM one (batch, head) of q, k, v or o takes: lanes padded."""
    return seq * max(head_dim, LANES) * itemsize


def _long_head(seq: int, head_dim: int, itemsize: int) -> bool:
    return seq >= LONG_HEAD_SEQ and _head_bytes(seq, head_dim, itemsize) >= LONG_HEAD_BYTES


def _streamed_head(seq: int, head_dim: int, itemsize: int) -> bool:
    """The backward pass holds nothing of such a head whole but its dq scratch."""
    return _head_bytes(seq, head_dim, itemsize) > LONG_HEAD_BYTES or head_dim > LANES


# --------------------------------------------------------------------------- masks by structure
EMPTY, WHOLE, CROSSED = 0, 1, 2  # what a mask keeps of a rectangle of scores: nothing, everything, some


class BlockDiffusion(NamedTuple):
    """Block-diffusion training's mask (BD3-LMs, SDAR) over a row of `2 * seq`
    positions: a clean copy of `seq` tokens first, then its noised copy, both
    cut into blocks of `block`. With `blk(p) = (p mod seq) // block`,

        query \\ key     clean copy               noised copy
        clean, block i   blocks <= i              none
        noised, block i  blocks < i (strict)      block i alone, both directions

    so every query keeps a key (its own block's), and the mask lies on or under
    the block-causal diagonal of the doubled row. Hashable and static: what
    `flash_attention(causal=)`, `xla_attention` and `blockwise_attention` take
    in place of True."""

    seq: int
    block: int

    def kept(self, rows, cols):
        """bool: whether query `rows` attends to key `cols` (int arrays of one
        shape, numpy's or jax's, positions in the doubled row)."""
        shift = self.block.bit_length() - 1
        blk = (lambda p: p >> shift) if 1 << shift == self.block else (lambda p: p // self.block)
        q_noised, k_noised = rows >= self.seq, cols >= self.seq
        strict = q_noised.astype(rows.dtype)
        q_blk, k_blk = blk(rows - self.seq * strict), blk(cols - self.seq * k_noised.astype(cols.dtype))
        return (k_noised & q_noised & (k_blk == q_blk)) | (~k_noised & (k_blk <= q_blk - strict))

    def tile_class(self, r0: int, r1: int, c0: int, c1: int) -> int:
        """EMPTY, WHOLE or CROSSED: what `kept` keeps of queries [r0, r1) x keys
        [c0, c1), from the corners' blocks alone (a rectangle that spans both
        copies is classed a copy at a time)."""
        L, parts = self.seq, set()
        for q_noised, (qa, qb) in enumerate(((r0, min(r1, L)), (max(r0, L), r1))):
            for k_noised, (ka, kb) in enumerate(((c0, min(c1, L)), (max(c0, L), c1))):
                if qa >= qb or ka >= kb:
                    continue
                q0, q1, k0, k1 = ((p - L * noised) // self.block for p, noised in (
                    (qa, q_noised), (qb - 1, q_noised), (ka, k_noised), (kb - 1, k_noised)))
                if k_noised:  # its own block alone, and a clean query sees none of the noised copy
                    whole = q_noised and q0 == q1 == k0 == k1
                    empty = not q_noised or k0 > q1 or k1 < q0
                else:  # blocks <= the query's, < where the query is noised
                    whole, empty = k1 <= q0 - q_noised, k0 > q1 - q_noised
                parts.add(WHOLE if whole else EMPTY if empty else CROSSED)
        return parts.pop() if len(parts) == 1 else CROSSED

    def dense(self, queries: int, keys: int):
        """The (queries, keys) bool of the whole doubled row, for the XLA forms."""
        assert queries == keys == 2 * self.seq, f"a row of {queries} x {keys} under a mask of 2 x {self.seq}"
        return self.kept(jax.lax.broadcasted_iota(jnp.int32, (queries, keys), 0),
                         jax.lax.broadcasted_iota(jnp.int32, (queries, keys), 1))


class SlidingWindow(NamedTuple):
    """A causal window: query i keeps key j where `0 <= i - j < window` (the
    query's own position and the `window - 1` before it; transformers' sliding
    mask). The band under the diagonal, `window` keys wide: a tile pair is cut
    by the diagonal above, by the window's edge below, or (a window narrower
    than a tile) by both. Every query keeps its own key. `window >= seq` is
    the causal diagonal, to the bit. Hashable and static, taken where
    `BlockDiffusion` is."""

    window: int

    def kept(self, rows, cols):
        """bool: whether query `rows` attends to key `cols` (int arrays of one shape, numpy's or jax's)."""
        return (rows >= cols) & (rows - cols < self.window)

    def tile_class(self, r0: int, r1: int, c0: int, c1: int) -> int:
        """EMPTY, WHOLE or CROSSED: what `kept` keeps of queries [r0, r1) x keys
        [c0, c1), from the rectangle's corners: the last query against the
        first key is the pair the diagonal drops last and the window first."""
        if c0 > r1 - 1 or r0 - (c1 - 1) >= self.window:  # all above the diagonal, or all behind the window
            return EMPTY
        if c1 - 1 <= r0 and (r1 - 1) - c0 < self.window:
            return WHOLE
        return CROSSED

    def dense(self, queries: int, keys: int):
        """The (queries, keys) bool of a whole row, for the XLA forms."""
        assert queries == keys, f"a window over {queries} queries x {keys} keys: self-attention of one row alone"
        return self.kept(jax.lax.broadcasted_iota(jnp.int32, (queries, keys), 0),
                         jax.lax.broadcasted_iota(jnp.int32, (queries, keys), 1))


def _dense_mask(causal, queries: int, keys: int):
    """(queries, keys) bool of `causal` (True: the diagonal, the last query on the last key), None for False."""
    if causal is False:
        return None
    if causal is True:
        return jnp.tril(jnp.ones((queries, keys), dtype=bool), k=keys - queries)
    return causal.dense(queries, keys)


# --------------------------------------------------------------------------- XLA form
def pack_keep(mask):
    """(..., queries, keys) bool -> (..., queries, spans * 128) int32, the packed
    form every `keep` argument of this file takes (`KEEP_SPAN`)."""
    *lead, keys = mask.shape
    spans = -(-keys // KEEP_SPAN)
    bits = jnp.pad(mask, [(0, 0)] * len(lead) + [(0, spans * KEEP_SPAN - keys)])
    bits = bits.reshape(*lead, spans, KEEP_BITS, LANES).astype(jnp.uint32)
    words = jnp.sum(bits << jnp.arange(KEEP_BITS, dtype=jnp.uint32)[:, None], axis=-2, dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(words, jnp.int32).reshape(*lead, spans * LANES)


def unpack_keep(keep, keys: int):
    """`pack_keep`'s inverse: (..., queries, spans * 128) int32 -> (..., queries, keys) bool."""
    *lead, width = keep.shape
    words = keep.reshape(*lead, width // LANES, 1, LANES)
    bits = (words >> jnp.arange(KEEP_BITS, dtype=jnp.int32)[:, None]) & 1
    return bits.reshape(*lead, width * KEEP_BITS)[..., :keys] != 0


def _repeat_kv(q, k, v):
    """k and v at the query heads' count: query head a reads key/value head
    `a // group` (what the pair-streamed kernels do by index map)."""
    group = q.shape[1] // k.shape[1]
    if group == 1:
        return k, v
    return jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)


def xla_attention(q, k, v, causal=True, sm_scale: Optional[float] = None, keep=None,
                  return_lse: bool = False):
    """Plain-XLA attention (fused well by the compiler; O(S^2) memory). `causal`:
    True, False or a mask by structure (`BlockDiffusion`, `SlidingWindow`). `keep`
    (batch, queries, spans * 128), packed (`pack_keep`): the keys each query may
    attend to, the same for every head. With `return_lse` also each row's
    log-sum-exp of the scaled scores it attends to, (batch, heads, queries) f32."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    k, v = _repeat_kv(q, k, v)
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * sm_scale
    mask = _dense_mask(causal, s.shape[-2], s.shape[-1])
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    if keep is not None:
        s = jnp.where(unpack_keep(keep, s.shape[-1])[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v, preferred_element_type=jnp.float32).astype(q.dtype)
    if return_lse:
        return o, jax.lax.stop_gradient(jax.scipy.special.logsumexp(s, axis=-1))
    return o


# --------------------------------------------------------------------------- tile schedule
def _diag_and_end(t, size, other, n_other, static):
    """For tile `t` (rows `[t*size, (t+1)*size)`) of one side of the score
    matrix, the tiles of the other side (size `other`, `n_other` of them) that
    the causal diagonal crosses: `[diag, end)`. The forward kernel asks with a
    Q tile and gets K tiles (those before `diag` lie wholly under the diagonal,
    those from `end` on wholly above it); the backward asks with a K tile and
    gets Q tiles (`[diag, end)` masked, `[end, n)` wholly under). Python ints in
    the unrolled schedule, traced values in the loop form."""
    diag = (t * size) // other
    end = ((t + 1) * size + other - 1) // other
    return diag, (min(end, n_other) if static else jnp.minimum(end, n_other))


def _tiles_under(mask, t: int, size: int, other: int, n_other: int, t_is_q: bool):
    """[(tile of the other side, whether the mask crosses the pair)] that tile
    `t` (of `size`; a Q tile where `t_is_q`, else a K tile) is walked with, in
    the order of the walk: what the pair-streamed schedules and the plan's
    counts are made from. `mask` False: every tile, none crossed; True: the
    causal diagonal's (`_diag_and_end`); else the pairs the mask does not class
    EMPTY, be they contiguous or not."""
    if mask is False:
        return [(o, False) for o in range(n_other)]
    if mask is True:
        diag, end = _diag_and_end(t, size, other, n_other, True)
        return [(j, j >= diag) for j in range(end)] if t_is_q else [(i, i < end) for i in range(diag, n_other)]
    mine, out = (t * size, (t + 1) * size), []
    for o in range(n_other):
        theirs = (o * other, (o + 1) * other)
        kind = mask.tile_class(*mine, *theirs) if t_is_q else mask.tile_class(*theirs, *mine)
        if kind != EMPTY:
            out.append((o, kind == CROSSED))
    return out


def _keeps_some(mask, r0: int, r1: int, c0: int, c1: int) -> bool:
    """Whether `mask` (True: the diagonal; else a mask by structure) keeps a score of queries [r0, r1) x keys [c0, c1)."""
    return c0 < r1 if mask is True else mask.tile_class(r0, r1, c0, c1) != EMPTY


def _span_keys(tile_k: int) -> int:
    """The block of keys a pair's live span is counted in: the forward's step down a K tile."""
    return math.gcd(tile_k, FWD_STEP_KEYS)


def _live_span(mask, i: int, j: int, tile_q: int, tile_k: int, crossed: bool):
    """(first block, blocks) of `_span_keys`: the run of K tile j outside which `mask` keeps no score of any
    query of Q tile i, asked of the mask itself a block of keys at a time. The whole tile for a pair that is
    not crossed (and for most that are: every square pair of the causal diagonal)."""
    unit = _span_keys(tile_k)
    if not crossed:
        return 0, tile_k // unit
    live = [b for b in range(tile_k // unit) if _keeps_some(
        mask, i * tile_q, (i + 1) * tile_q, j * tile_k + b * unit, j * tile_k + (b + 1) * unit)]
    return live[0], live[-1] + 1 - live[0]


def _kept_in_pair(mask, i, j, tile_q: int, tile_k: int, shape, q_axis: int, start=None):
    """bool `shape`: the scores of the crossed pair (Q tile i, K tile j) that
    `mask` keeps, queries along `q_axis` of the tile and keys along the other,
    from iotas and the pair's offsets (scalars of the kernel); with `start`, of
    the pair's keys from that one of the K tile on (its live span's first)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    k0 = lambda: j * tile_k if start is None else j * tile_k + start
    if isinstance(mask, bool):
        # The diagonal: `row - col` inside a tile is one constant a program, a pair adds its offset. (A call
        # that is not causal traces a crossed pair's branch too, and never takes it.)
        return rows - cols >= k0() - i * tile_q
    return mask.kept(rows + i * tile_q, cols + k0())


def _for(lo, hi, body, carry, static):
    """`fori_loop`, or the same loop unrolled at trace time when its bounds
    are Python ints: every slice in `body` is then static."""
    if not static:
        return jax.lax.fori_loop(lo, hi, body, carry)
    for t in range(lo, hi):
        carry = body(t, carry)
    return carry


def _tile(t, size, static):
    return pl.ds(t * size, size) if static else pl.ds(pl.multiple_of(t * size, size), size)


def _causal_mask(tile_q, tile_k):
    """`keep(i, j)`: which scores of tile (i, j) lie on or under the diagonal.
    `row - col` inside a tile is one constant per program; a tile adds only
    its offset, and in a square unrolled schedule every diagonal tile has
    offset 0, so they all share one `row >= col` pattern."""
    diff = (jax.lax.broadcasted_iota(jnp.int32, (tile_q, tile_k), 0)
            - jax.lax.broadcasted_iota(jnp.int32, (tile_q, tile_k), 1))
    cache = {}

    def keep(i, j):
        offset = j * tile_k - i * tile_q
        if not isinstance(offset, int):
            return diff >= offset
        if offset not in cache:
            cache[offset] = diff >= offset
        return cache[offset]

    return keep


def _first_bit(j, tile_k):
    """The bit of a packed selection's words that holds the first 128 keys of K tile `j` (`KEEP_SPAN`)."""
    return (j % (KEEP_SPAN // tile_k)) * (tile_k // LANES)


def _keep_tile(keep_ref, j, tile_k, start=None, keys=None):
    """The selection's (tile_q, tile_k) bool for K tile `j`, out of the packed
    (1, tile_q, 128) block that holds it: tile_k / 128 bits of every word, a
    lane tile each. With `start` (a scalar of the kernel, whole lane tiles) and
    `keys`: of the tile's `keys` keys from that one on, (tile_q, keys)."""
    bits = tile_k // LANES
    words = keep_ref[0]
    first = _first_bit(j, tile_k)
    if start is not None:
        first, bits = first + start // LANES, keys // LANES
    return jnp.concatenate([(words >> (first + b)) & 1 for b in range(bits)], axis=1) != 0


def _keep_spec(heads: int, tile_q: int, tile_k: int, q_row: int, k_row: int):
    """The block of a pair's selection under a scalar-prefetched schedule whose
    rows `q_row` and `k_row` are the pair's tiles: the row's own (grid axis 0
    counts batch * heads), the Q tile's, the span the K tile lies in."""
    assert KEEP_SPAN % tile_k == 0 and tile_k % LANES == 0, f"a K tile of {tile_k} splits no span of {KEEP_SPAN}"
    per_span = KEEP_SPAN // tile_k
    return pl.BlockSpec((1, tile_q, LANES),
                        lambda b, t, steps: (b // heads, steps[q_row, t], steps[k_row, t] // per_span))


# --------------------------------------------------------------------------- forward kernel
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale, causal, tile_q, tile_k, static):
    """Per Q tile the online softmax walks the K tiles on or under the
    diagonal, unmasked ones first. Unrolled, one program holds a whole
    (batch, head) and visits its Q tiles in turn; in the loop form a program
    is one Q tile (grid axis 1) and `q_ref`, `o_ref`, `lse_ref` are that tile."""
    seq, d = k_ref.shape[1], v_ref.shape[2]  # the accumulator is as wide as the values
    n_q, n_k = seq // tile_q, seq // tile_k
    keep = _causal_mask(tile_q, tile_k) if causal else None

    def q_tile(i, rows):
        # Matmul operands stay in their input dtype (bf16 in training): f32 x f32
        # dots run the MXU at a fraction of its bf16 rate; accumulation is f32 via
        # preferred_element_type either way. sm_scale folds into q once per Q
        # tile instead of rescaling every (tile_q x tile_k) score matrix.
        q = (q_ref[0, rows, :].astype(jnp.float32) * sm_scale).astype(q_ref.dtype)

        def k_tile(masked):
            def body(j, carry):
                # Row statistics stay (tile_q, 1), as the refs are: no
                # relayout between a reduction and the broadcast that uses it.
                m_prev, l_prev, acc = carry
                cols = _tile(j, tile_k, static)
                k = k_ref[0, cols, :]
                v = v_ref[0, cols, :]
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
                )  # (tile_q, tile_k)
                if masked:
                    s = jnp.where(keep(i, j), s, NEG_INF)
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m_prev - m_new)
                l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
                acc = acc * alpha + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                return m_new, l_new, acc

            return body

        carry = (jnp.full((tile_q, 1), NEG_INF, jnp.float32),
                 jnp.zeros((tile_q, 1), jnp.float32),
                 jnp.zeros((tile_q, d), jnp.float32))
        if causal:
            diag, end = _diag_and_end(i, tile_q, tile_k, n_k, static)
            carry = _for(0, diag, k_tile(False), carry, static)
            carry = _for(diag, end, k_tile(True), carry, static)
        else:
            carry = _for(0, n_k, k_tile(False), carry, static)
        m, l, acc = carry
        l = jnp.maximum(l, 1e-30)
        o_ref[0, rows, :] = (acc / l).astype(o_ref.dtype)
        lse_ref[0, rows, :] = m + jnp.log(l)

    if static:
        for i in range(n_q):
            q_tile(i, _tile(i, tile_q, static))
    else:
        q_tile(pl.program_id(1), slice(None))


def _specs(seq, plan, tile):
    """(grid axes after batch*heads, spec of a whole head, spec of the tile a
    program owns). Unrolled: one program per (batch, head), and what it owns
    is the whole head too. Loop form: one program per tile of size `tile`."""
    head = lambda width: pl.BlockSpec((1, seq, width), lambda b, *_: (b, 0, 0))
    if plan.unrolled:
        return (), head, head
    return (seq // tile,), head, lambda width: pl.BlockSpec((1, tile, width), lambda b, t: (b, t, 0))


def _compiler_params(interpret, *semantics):
    # No `vmem_limit_bytes`: a limit on one call makes XLA set that VMEM aside
    # in every instruction of the program (PERF.md section 7).
    return None if interpret else pltpu.CompilerParams(dimension_semantics=semantics)


def _fwd(q, k, v, causal, sm_scale, plan, interpret):
    bh, seq, d = q.shape
    dv = v.shape[-1]  # the values' width, and the output's: the keys' own where they differ (`flash_attention`)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal,
        tile_q=plan.tile_q, tile_k=plan.tile_k, static=plan.unrolled,
    )
    tiles, head, mine = _specs(seq, plan, plan.tile_q)
    with jax.named_scope(plan.scope):
        o, lse = pl.pallas_call(
            kernel,
            grid=(bh, *tiles),
            in_specs=[mine(d), head(d), head(dv)],
            # (bh, seq, 1): TPU block specs constrain the last two dims, so the
            # per-row stats carry a trailing unit dim to stay tileable.
            out_specs=[mine(dv), mine(1)],
            out_shape=[
                jax.ShapeDtypeStruct((bh, seq, dv), q.dtype),
                jax.ShapeDtypeStruct((bh, seq, 1), jnp.float32),
            ],
            interpret=interpret,
            name="flash_fwd",
            compiler_params=_compiler_params(interpret, "parallel", *["parallel"] * len(tiles)),
            # As XLA has always been told (one head's full square). What it is
            # told steers its schedule round the call, on four chips visibly,
            # so it changes in a PR of its own (PERF.md section 7).
            cost_estimate=pl.CostEstimate(
                flops=2 * seq * seq * (d + dv),
                bytes_accessed=2 * seq * (d + dv) * q.dtype.itemsize,
                transcendentals=seq * seq,
            ),
        )(q, k, v)
    return o, lse


def _fwd_schedule(seq: int, plan: "KernelPlan", causal):
    """The pair-streamed forward pass's steps, int32 (7, steps): for each the Q
    tile, the K tile, whether it is the Q tile's first pair, a pair the
    mask crosses, the Q tile's last pair, and the pair's live span of keys
    (`_live_span`: first block, blocks). Q tiles in turn, under each the K
    tiles the mask leaves it (`_tiles_under`: on or under the diagonal when
    causal): a Q tile's output block is the same over its run of steps and is
    written in the last."""
    n_q, n_k = seq // plan.tile_q, seq // plan.tile_k
    steps = []
    for i in range(n_q):
        tiles = _tiles_under(causal, i, plan.tile_q, plan.tile_k, n_k, True)
        assert tiles, f"Q tile {i} keeps no key under {causal}: its output would never be written"
        steps += [(i, j, n == 0, crossed, n == len(tiles) - 1, *_live_span(causal, i, j, plan.tile_q, plan.tile_k, crossed))
                  for n, (j, crossed) in enumerate(tiles)]
    return np.asarray(steps, np.int32).T


def _walk_scope(steps, seq: int, tile_q: int, tile_k: int):
    """(`KernelPlan.scope` from a pair-streamed schedule itself, what the walk scores), the two `jax.named_scope`s
    round such a call: the steps a program of the call walks (its grid's second axis) of the tile pairs of the
    square, at the tiles the call runs, and the blocks of keys (`_span_keys`) inside the steps' live spans of those
    the steps hold."""
    walked = steps.shape[1] * (tile_k // _span_keys(tile_k))
    return f"tiles_{steps.shape[1]}of{(seq // tile_q) * (seq // tile_k)}", f"keys_{steps[-1].sum()}of{walked}"


def _short_spans(steps, tile_k: int):
    """The lengths, in keys, of a schedule's live spans that are shorter than the K tile: () where every pair
    scores its whole tile, and the kernels are then the programs they were before a schedule named a span."""
    return tuple(sorted({int(n) * _span_keys(tile_k) for n in steps[-1]} - {tile_k}))


def _keep_tile_t(keep_ref, j, tile_k):
    """`_keep_tile` turned over, (tile_k, tile_q) bool, keys down and queries
    across: bit b of the turned words is a (128, tile_q) slab of keys."""
    bits = tile_k // LANES
    words = keep_ref[0].T  # (128, tile_q)
    first = _first_bit(j, tile_k)
    return jnp.concatenate([(words >> (first + b)) & 1 for b in range(bits)], axis=0) != 0


def _scores_t(k, qs):
    """s^T (keys, queries) f32 of one head: keys down the sublanes, queries across the lanes."""
    return jax.lax.dot_general(k, qs, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _softmax_step(s, m_prev, l_prev):
    """One step of the online softmax, keys down: the maximum and the sum run
    down the sublanes, and what belongs to a query (m, l, alpha) is a (1,
    queries) row. A masked score is `NEG_INF` and `m_prev` at least `FLOOR`, so
    its exp is 0 by itself. -> (p, m_new, l_new, alpha)."""
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    return p, m_new, l_prev * alpha + jnp.sum(p, axis=0, keepdims=True), alpha


def _values_t(v_t, p):
    """o^T's share of a step, (d, queries) f32, from `v_t` (d, keys) and `p` (keys, queries)."""
    return jax.lax.dot_general(v_t, p, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


@jax.jit
def _block_step(k, v_t, qs, bias, m, l, acc):
    """One block of keys against one block of a head's queries: the head's (m, l, o^T) of those
    queries after it. Jitted: the kernel holds up to `FWD_BLOCKS_A_TRIP` of these a trip and a step traces
    the kernel four or five times; so they are traced once a process and not one by one."""
    s = _scores_t(k, qs)
    if bias is not None:
        s = s + bias
    p, m, l, alpha = _softmax_step(s, m, l)
    return m, l, acc * alpha + _values_t(v_t, p.astype(v_t.dtype))


def _fwd_pairs_kernel(steps_ref, q_ref, k_ref, v_ref, *refs, sm_scale, tile_q, tile_k, has_keep, mask=True,
                      a_trip, spans=False):
    """The forward pass with a program a (Q tile, K tile) pair (`_fwd_schedule`,
    grid axis 1, sequential) of the `heads` consecutive query heads that the
    program takes (`q_ref` (1, heads, tile_q, d), held once a Q tile and scaled
    into `qs` in its first pair) and of the key/value heads they read (`k_ref`,
    `v_ref` (1, kv heads, tile_k, d): one that the heads share, or one a group
    of them): k, v and the packed selection are the pair's own tiles, fetched
    once for all the heads. Inside, a tile is (keys, queries): the pair's mask
    (the selection's, the diagonal's where it crosses) becomes one additive
    `bias` that every head reads (`mask`: what a crossed pair's is made from,
    `_kept_in_pair`), v is turned once, and a head's running
    maximum, sum and f32 output `o^T` (d, tile_q) stay in VMEM over the Q
    tile's pairs; the output is turned back and written in the last.

    The pair itself is walked a (`FWD_STEP_KEYS`, `FWD_STEP_QUERIES`) block of
    scores at a time, the online softmax's step (`_block_step`): 32 registers
    of scores that never leave the register file, where a whole (tile_k,
    tile_q) tile is stored between its maximum and its exponentials and loaded
    again. A head's blocks of the same queries are a chain; the chains of a
    program are independent and lie side by side in a trip of the loop over
    key blocks (`a_trip` of them a trip), where the compiler interleaves them
    so that the MXU does not wait for the vector unit (PERF.md section 6, PR 44).

    `spans`: the schedule names a live span of keys shorter than the K tile
    for some pair (`_short_spans`). A masked pair's mask is then made and its
    key blocks walked over its span alone, by loops whose bounds are the
    schedule's values: outside it every score is masked, and its exponential
    is 0.0 against any maximum from `FLOOR` up, so nothing of o or lse changes."""
    keep_ref = refs[0] if has_keep else None
    o_ref, lse_ref, qs, m_acc, l_acc, o_acc, bias = refs[has_keep:]
    heads, kv_heads = q_ref.shape[1], k_ref.shape[1]
    t = pl.program_id(1)
    i, j = steps_ref[0, t], steps_ref[1, t]
    first, crossed, last = (steps_ref[r, t] == 1 for r in (2, 3, 4))
    width, depth = math.gcd(tile_q, FWD_STEP_QUERIES), _span_keys(tile_k)
    span = (steps_ref[5, t], steps_ref[5, t] + steps_ref[6, t]) if spans else (0, tile_k // depth)  # key blocks

    @pl.when(first)
    def _():
        for h in range(heads):
            qs[h] = (q_ref[0, h].astype(jnp.float32) * sm_scale).astype(qs.dtype)
        # Far above `NEG_INF`, far below any score: a row that has kept no key yet sums zeros.
        m_acc[...] = jnp.full_like(m_acc, FLOOR)
        l_acc[...] = jnp.zeros_like(l_acc)
        o_acc[...] = jnp.zeros_like(o_acc)

    def mask_to_bias(diagonal):
        def run():
            if diagonal and spans:  # the span's rows alone, a block of keys at a time: the loop reads no others
                words = keep_ref[0].T if has_keep else None  # `_keep_tile_t`, a bit at a time

                def block(b, carry):
                    kept = _kept_in_pair(mask, i, j, tile_q, tile_k, (depth, tile_q), 1, b * depth)
                    if has_keep:
                        kept &= ((words >> (_first_bit(j, tile_k) + b)) & 1) != 0
                    bias[pl.ds(pl.multiple_of(b * depth, depth), depth)] = jnp.where(kept, 0.0, NEG_INF)
                    return carry

                jax.lax.fori_loop(*span, block, 0)
                return
            kept = _keep_tile_t(keep_ref, j, tile_k) if has_keep else None
            if diagonal:
                under = _kept_in_pair(mask, i, j, tile_q, tile_k, (tile_k, tile_q), 1)
                kept = under if kept is None else kept & under
            bias[...] = jnp.where(kept, 0.0, NEG_INF)
        return run

    def heads_of_pair(masked):
        def run():
            lo, hi = span if masked else (0, tile_k // depth)  # every key of a pair with no mask is live

            def trip(n, carry):
                for u in range(a_trip):
                    keys = pl.ds(pl.multiple_of((n * a_trip + u) * depth, depth), depth)
                    k = [k_ref[0, g, keys] for g in range(kv_heads)]
                    v_t = [v_ref[0, g, keys].T for g in range(kv_heads)]
                    for h in range(heads):
                        g = h * kv_heads // heads  # the head's key/value head among the program's
                        for c in range(tile_q // width):
                            row, cols = slice(h, h + 1), slice(c * width, (c + 1) * width)
                            m_acc[row, cols], l_acc[row, cols], o_acc[h, :, cols] = _block_step(
                                k[g], v_t[g], qs[h, cols], bias[keys, cols] if masked else None,
                                m_acc[row, cols], l_acc[row, cols], o_acc[h, :, cols])
                return carry

            jax.lax.fori_loop(lo // a_trip, hi // a_trip, trip, 0)
        return run

    if has_keep:  # every pair is masked: one body, behind whichever mask the pair has
        pl.when(crossed)(mask_to_bias(True))
        pl.when(jnp.logical_not(crossed))(mask_to_bias(False))
        heads_of_pair(True)()
    else:
        @pl.when(crossed)
        def _():
            mask_to_bias(True)()
            heads_of_pair(True)()

        pl.when(jnp.logical_not(crossed))(heads_of_pair(False))

    @pl.when(last)
    def _():
        for h in range(heads):
            l = l_acc[h:h + 1]
            o_ref[0, h] = (o_acc[h] / jnp.maximum(l, 1e-30)).T.astype(o_ref.dtype)
            # A row that kept no key at all: o = 0, and the XLA form's log-sum-exp of nothing but `NEG_INF`.
            lse_ref[0, h:h + 1] = jnp.where(l > 0, m_acc[h:h + 1] + jnp.log(l), NEG_INF)


def _pair_specs(plan, d: int, group: int, q_row: int, k_row: int):
    """(spec of a Q tile `width` wide, of a key/value tile) under a
    scalar-prefetched schedule: a query head reads key/value head `head // group`."""
    q_tile = lambda width, row=q_row: pl.BlockSpec(
        (1, plan.tile_q, width), lambda b, t, steps: (b, steps[row, t], 0))
    kv = (lambda b: b) if group == 1 else (lambda b: b // group)
    k_tile = pl.BlockSpec((1, plan.tile_k, d), lambda b, t, steps: (kv(b), steps[k_row, t], 0))
    return q_tile, k_tile


def _fwd_pairs_bytes(heads: int, kv_heads: int, tile_q: int, tile_k: int, d: int, itemsize: int) -> int:
    """The VMEM a program of `_fwd_pairs_kernel` holds, counted from above: of its
    `heads` query heads q once and its scaled copy, o's block twice, the f32 `o^T`,
    the statistics' rows; of its `kv_heads` key/value heads k and v twice (the
    pipeline fetches them ahead of a pair) and v turned; the selection twice; the
    (tile_k, tile_q) f32 bias and as much again for what the mask is made from.
    The smallest `vmem_limit_bytes` that compiles ahead of time for the v5e (bf16,
    PR 44): 8 heads on one x 512 on 1,024 keys at d = 128 with a selection 10.3 MiB
    for the 11.8 counted here, 8 x 256 5.7 for 6.5, 32 x 256 14.8 for 15.6, 32 x 128
    (no selection) 7.9 for 8.4, one head x 512 on 512 keys at d = 256 4.5 for 5.3."""
    wide = max(d, LANES)
    rows = -(-heads // 8) * 8
    of_q_tile = heads * tile_q * (4 * wide * itemsize + d * 4) + 4 * rows * tile_q * 4
    of_pair = kv_heads * 5 * tile_k * wide * itemsize + 2 * tile_q * LANES * 4
    return of_q_tile + of_pair + 2 * tile_k * tile_q * 4


def _fwd_pairs_plan(group: int, row_heads: int, d: int, itemsize: int, plan: "KernelPlan"):
    """(query heads a program takes, its Q tile) of the pair-streamed forward
    pass, from the shapes and `_fwd_pairs_bytes`. The heads are consecutive ones
    of the `row_heads` that share a selection: part of a key/value head's
    `group`, the group, or several groups with their key/value heads. The Q
    tile is `plan`'s or a half, a quarter of it (the backward pass keeps the
    plan's). Of those that hold `FWD_PAIRS_VMEM_BYTES` or less, the most heads x
    queries: a program's chains are what fills the MXU's pipeline (PERF.md
    section 6, PR 44: one head a program is 12 % slower than the form this
    replaced); more heads before longer tiles (k, v and the mask are made once a
    program); where nothing fits, the least."""
    tiles = [plan.tile_q] + [plan.tile_q // n for n in (2, 4) if plan.tile_q % (n * LANES) == 0]
    shapes = [(heads, tile_q) for heads in range(row_heads, 0, -1)
              if row_heads % heads == 0 and (group % heads == 0 or heads % group == 0) for tile_q in tiles]
    kv_heads = lambda shape: max(shape[0] // group, 1)
    size = lambda shape: _fwd_pairs_bytes(shape[0], kv_heads(shape), shape[1], plan.tile_k, d, itemsize)
    fitting = [shape for shape in shapes if size(shape) <= FWD_PAIRS_VMEM_BYTES]
    if not fitting:
        return min(shapes, key=size)
    return max(fitting, key=lambda shape: (shape[0] * shape[1], -kv_heads(shape), shape[0]))


def _fwd_pairs(q, k, v, keep, causal, sm_scale, plan, interpret):
    """q (batch * heads, seq, d); k, v (batch * kv heads, seq, d); keep
    (batch, seq, spans * 128) or None. -> o as q, lse (batch * heads, seq, 1):
    it leaves the kernel lane-dense, a row a head, and is reshaped to what
    `_bwd_pairs` reads."""
    bh, seq, d = q.shape
    group = bh // k.shape[0]
    # Without a selection nothing ties a program's heads to one row of the batch.
    row_heads = bh if keep is None else bh // keep.shape[0]
    heads, tile_q = _fwd_pairs_plan(group, row_heads, d, q.dtype.itemsize, plan)
    tile_k = plan.tile_k
    steps = _fwd_schedule(seq, plan._replace(tile_q=tile_q), causal)
    scopes, spans = _walk_scope(steps, seq, tile_q, tile_k), bool(_short_spans(steps, tile_k))
    # Key blocks a trip of the kernel's loop: as many as keep a trip's blocks at `FWD_BLOCKS_A_TRIP` or fewer, one at
    # least, and a number that divides every live span of the schedule, so that a trip lies inside one.
    blocks, chains = tile_k // _span_keys(tile_k), heads * (tile_q // math.gcd(tile_q, FWD_STEP_QUERIES))
    a_trip = max(n for n in range(1, blocks + 1) if blocks % n == 0 and not (steps[5:] % n).any()
                 and n * chains <= max(FWD_BLOCKS_A_TRIP, chains))
    if not spans:  # the kernel reads the rows it read before a schedule named a span
        steps = steps[:5]
    # Grid axis 0: `heads` query heads a program, on `kv_heads` key/value heads of their own or on one
    # that `parts` programs share.
    programs, kv_heads, parts = bh // heads, max(heads // group, 1), max(group // heads, 1)
    once = pl.Buffered(1)  # a block that changes with the Q tile alone: nothing to fetch ahead of a pair
    q_tile = lambda **kw: pl.BlockSpec((1, heads, tile_q, d), lambda b, t, steps: (b, 0, steps[0, t], 0), **kw)
    k_tile = pl.BlockSpec((1, kv_heads, tile_k, d), lambda b, t, steps: (b // parts, 0, steps[1, t], 0))
    in_specs = [q_tile(pipeline_mode=once), k_tile, k_tile]
    operands = [q.reshape(programs, heads, seq, d), *(x.reshape(-1, kv_heads, seq, d) for x in (k, v))]
    if keep is not None:
        in_specs.append(_keep_spec(programs // keep.shape[0], tile_q, tile_k, 0, 1))
        operands.append(keep)
    # `group_<n>`: which form a trace's `flash_fwd` ran (the heads a program takes).
    with jax.named_scope(scopes[0]), jax.named_scope(scopes[1]), jax.named_scope(f"group_{heads}"):
        o, lse = pl.pallas_call(
            functools.partial(_fwd_pairs_kernel, sm_scale=sm_scale, tile_q=tile_q, tile_k=tile_k,
                              has_keep=keep is not None, mask=causal, a_trip=a_trip, spans=spans),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(programs, steps.shape[1]),
                in_specs=in_specs,
                out_specs=[q_tile(), pl.BlockSpec((1, heads, tile_q), lambda b, t, steps: (b, 0, steps[0, t]))],
                scratch_shapes=[pltpu.VMEM((heads, tile_q, d), q.dtype),
                                pltpu.VMEM((heads, tile_q), jnp.float32),
                                pltpu.VMEM((heads, tile_q), jnp.float32),
                                pltpu.VMEM((heads, d, tile_q), jnp.float32),
                                pltpu.VMEM((tile_k, tile_q), jnp.float32)],
            ),
            out_shape=[jax.ShapeDtypeStruct((programs, heads, seq, d), q.dtype),
                       jax.ShapeDtypeStruct((programs, heads, seq), jnp.float32)],
            interpret=interpret,
            name="flash_fwd",
            compiler_params=_compiler_params(interpret, "parallel", "arbitrary"),
        )(jnp.asarray(steps), *operands)
    return o.reshape(bh, seq, d), lse.reshape(bh, seq, 1)


# --------------------------------------------------------------------------- backward kernel
def _pair_grads(q_ref, do_ref, lse_ref, delta_ref, dq_acc, rows, acc_rows, k, v, keep, sm_scale,
                dk, dv):
    """One (Q tile, K tile) pair of the backward pass: s and p recomputed once,
    `dq_acc[acc_rows]` gains the pair's dq, and (dk, dv) come back with the
    pair's share added. `rows` are the Q tile's rows in the four refs (all of
    a ref that is the tile itself); `keep` masks a pair the diagonal crosses."""
    q = q_ref[0, rows, :]
    do = do_ref[0, rows, :]
    qs = (q.astype(jnp.float32) * sm_scale).astype(q.dtype)
    s = jax.lax.dot_general(
        qs, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (tile_q, tile_k)
    if keep is not None:
        s = jnp.where(keep, s, NEG_INF)
    p = jnp.exp(s - lse_ref[0, rows, :])
    dv = dv + jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = (p * (dp - delta_ref[0, rows, :]) * sm_scale).astype(q.dtype)
    dk = dk + jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    dq_acc[acc_rows, :] += jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    return dk, dv


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, *,
                sm_scale, causal, tile_q, tile_k, static):
    """ONE fused kernel. Per K tile, dk/dv accumulate over the Q tiles on or
    under the diagonal and each pair adds its dq contribution to the f32 VMEM
    scratch (seq, d); s/p are recomputed once per tile pair. Unrolled, one
    program walks the K tiles of a whole (batch, head); in the loop form a
    program is one K tile (grid axis 1, sequential: the scratch lives across
    the K tiles of a head) and `k_ref`, `v_ref`, `dk_ref`, `dv_ref` are that tile."""
    seq = q_ref.shape[1]
    n_q, n_k = seq // tile_q, seq // tile_k
    keep = _causal_mask(tile_q, tile_k) if causal else None

    def k_tile(j, cols):
        k = k_ref[0, cols, :]
        v = v_ref[0, cols, :]

        def q_tile(masked):
            def body(i, carry):
                rows = _tile(i, tile_q, static)
                return _pair_grads(q_ref, do_ref, lse_ref, delta_ref, dq_acc, rows, rows, k, v,
                                   keep(i, j) if masked else None, sm_scale, *carry)

            return body

        carry = (jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32))
        if causal:
            diag, end = _diag_and_end(j, tile_k, tile_q, n_q, static)
            carry = _for(diag, end, q_tile(True), carry, static)
            carry = _for(end, n_q, q_tile(False), carry, static)
        else:
            carry = _for(0, n_q, q_tile(False), carry, static)
        dk, dv = carry
        dk_ref[0, cols, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, cols, :] = dv.astype(dv_ref.dtype)

    def zero():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def flush():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)

    if static:
        zero()
        for j in range(n_k):
            k_tile(j, _tile(j, tile_k, static))
        flush()
    else:
        j = pl.program_id(1)
        pl.when(j == 0)(zero)
        k_tile(j, slice(None))
        pl.when(j == n_k - 1)(flush)


def _pair_schedule(seq: int, plan: "KernelPlan", causal):
    """The pair-streamed backward pass's steps, int32 (8, steps): for each the
    Q tile, the K tile, the dq tile its output block is, whether it is the
    K tile's first pair, a pair the mask crosses, the pair after which its
    Q tile's dq is whole, and the pair's live span of keys (`_live_span`:
    first block, blocks). K tiles in turn, under each the Q tiles the mask
    leaves it (`_tiles_under`: on or under the diagonal when causal): a Q
    tile's last pair comes later the later the tile (asserted: a mask on or
    under a block-causal diagonal has it so), so the dq block of a step is the
    next tile to become whole, and it is written in that step alone."""
    n_q, n_k = seq // plan.tile_q, seq // plan.tile_k
    pairs = []
    for j in range(n_k):
        tiles = _tiles_under(causal, j, plan.tile_k, plan.tile_q, n_q, False)
        assert tiles, f"K tile {j} is kept by no query under {causal}: its dk and dv would never be written"
        pairs += [(i, j, n == 0, crossed) for n, (i, crossed) in enumerate(tiles)]
    whole_at = {i: t for t, (i, *_) in enumerate(pairs)}
    assert sorted(whole_at.values()) == [whole_at[i] for i in range(n_q)], f"no schedule under {causal}"
    steps, due = [], 0
    for t, (i, j, first, masked) in enumerate(pairs):
        steps.append((i, j, due, first, masked, whole_at[i] == t,
                      *_live_span(causal, i, j, plan.tile_q, plan.tile_k, masked)))
        due = min(due + (whole_at[due] == t), n_q - 1)
    return np.asarray(steps, np.int32).T


def _bwd_pairs_kernel(steps_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                      sm_scale, tile_q, tile_k, has_keep=False, mask=True, short_spans=()):
    """The fused backward pass with a program a (Q tile, K tile) pair
    (`_pair_schedule`, grid axis 1, sequential): every operand is the pair's
    own tile, streamed by the pipeline, and all a head keeps in VMEM is its
    f32 dq (seq, d) beside the K tile's f32 dk and dv. `keep_ref` (with
    `has_keep`) is the pair's tile of the packed selection, which then masks
    every pair, beside the diagonal where it crosses; `mask` is what a crossed
    pair's own mask is made from (`_kept_in_pair`). `short_spans`: the lengths
    of the schedule's live spans shorter than the K tile (`_short_spans`). A
    crossed pair of such a span runs its products, its mask and its
    exponentials over those keys alone, a slice of k, v, dk and dv that starts
    where the schedule says: every score outside it is masked and its p is
    exp(`NEG_INF` - lse) = 0.0, so nothing of dq, dk or dv changes. One more
    static form of the pair a length; none where every span is the whole tile."""
    keep_ref = refs[0] if has_keep else None
    dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = refs[has_keep:]
    t = pl.program_id(1)
    i, j = steps_ref[0, t], steps_ref[1, t]
    first, masked, whole = (steps_ref[r, t] == 1 for r in (3, 4, 5))
    acc_rows = _tile(i, tile_q, False)

    @pl.when(t == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(first)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def pair(crossed, keys=tile_k):
        def run():
            start, cols = None, ...
            if keys != tile_k:  # the live span: `keys` keys of the tile from `start` on
                start = pl.multiple_of(steps_ref[6, t] * _span_keys(tile_k), _span_keys(tile_k))
                cols = pl.ds(start, keys)
            keep = _kept_in_pair(mask, i, j, tile_q, tile_k, (tile_q, keys), 0, start) if crossed else None
            if has_keep:
                kept = _keep_tile(keep_ref, j, tile_k, start, keys)
                keep = kept if keep is None else keep & kept
            dk_acc[cols], dv_acc[cols] = _pair_grads(
                q_ref, do_ref, lse_ref, delta_ref, dq_acc, slice(None), acc_rows, k_ref[0, cols], v_ref[0, cols],
                keep, sm_scale, dk_acc[cols], dv_acc[cols])
        return run

    if short_spans:
        scored = steps_ref[7, t] * _span_keys(tile_k)  # the keys of the pair's live span
        for keys in (*short_spans, tile_k):
            pl.when(jnp.logical_and(masked, scored == keys))(pair(True, keys))
    else:
        pl.when(masked)(pair(True))
    pl.when(jnp.logical_not(masked))(pair(False))

    @pl.when(whole)
    def _():
        dq_ref[0] = dq_acc[acc_rows, :].astype(dq_ref.dtype)

    if isinstance(mask, bool):  # every K tile's walk ends at the last Q tile
        last = i == dq_acc.shape[0] // tile_q - 1
    else:  # the next step is another K tile's, or there is none
        end = pl.num_programs(1) - 1
        last = jnp.logical_or(t == end, steps_ref[1, jnp.minimum(t + 1, end)] != j)

    @pl.when(last)  # the K tile's last pair
    def _():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_pairs(q, k, v, do, lse, delta, causal, sm_scale, plan, interpret, keep=None):
    """k and v may hold fewer heads than q (batch * kv heads, seq, d): dk and dv
    still come back a query head each, for the caller to sum over the group."""
    bh, seq, d = q.shape
    dv = v.shape[-1]  # v, do and dv in the values' own width
    steps = _pair_schedule(seq, plan, causal)
    scopes, short_spans = _walk_scope(steps, seq, plan.tile_q, plan.tile_k), _short_spans(steps, plan.tile_k)
    if not short_spans:  # the kernel reads the rows it read before a schedule named a span
        steps = steps[:6]
    tile = lambda size, width, row: pl.BlockSpec(
        (1, size, width), lambda b, t, steps: (b, steps[row, t], 0))
    q_tile, k_tile, stat = tile(plan.tile_q, d, 0), tile(plan.tile_k, d, 1), tile(plan.tile_q, 1, 0)
    do_tile, v_tile = tile(plan.tile_q, dv, 0), tile(plan.tile_k, dv, 1)
    kv_tile = k_tile if k.shape[0] == bh else _pair_specs(plan, d, bh // k.shape[0], 0, 1)[1]
    assert dv == d or k.shape[0] == bh, "values of a width of their own under grouped key/value heads: not written"
    extra = [] if keep is None else [keep]
    keep_specs = [_keep_spec(bh // keep.shape[0], plan.tile_q, plan.tile_k, 0, 1)] if extra else []
    with jax.named_scope(scopes[0]), jax.named_scope(scopes[1]):
        return pl.pallas_call(
            functools.partial(_bwd_pairs_kernel, sm_scale=sm_scale, tile_q=plan.tile_q, tile_k=plan.tile_k,
                              has_keep=keep is not None, mask=causal, short_spans=short_spans),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(bh, steps.shape[1]),
                in_specs=[q_tile, kv_tile, kv_tile if dv == d else v_tile, do_tile, stat, stat] + keep_specs,
                out_specs=[tile(plan.tile_q, d, 2), k_tile, v_tile],
                scratch_shapes=[pltpu.VMEM((seq, d), jnp.float32),
                                pltpu.VMEM((plan.tile_k, d), jnp.float32),
                                pltpu.VMEM((plan.tile_k, dv), jnp.float32)],
            ),
            out_shape=[jax.ShapeDtypeStruct((bh, seq, width), q.dtype) for width in (d, d, dv)],
            interpret=interpret,
            name="flash_bwd",
            compiler_params=_compiler_params(interpret, "parallel", "arbitrary"),
        )(jnp.asarray(steps), q, k, v, do, lse, delta, *extra)


def _bwd(causal, sm_scale, plan, interpret, res, g):
    q, k, v, o, lse = res
    do = g
    bh, seq, d = q.shape
    dv = v.shape[-1]  # v, do and dv in the values' own width
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)[..., None]  # (bh, seq, 1)
    if not plan.unrolled and _streamed_head(seq, d, q.dtype.itemsize):
        return _bwd_pairs(q, k, v, do, lse, delta, causal, sm_scale, plan, interpret)
    tiles, head, mine = _specs(seq, plan, plan.tile_k)
    whole, stats = head, head(1)
    if not plan.unrolled and _long_head(seq, d, q.dtype.itemsize):
        # A head's q, do and row statistics change only when the grid moves to
        # the next head; (seq, 1) in f32 takes a whole lane tile a row in
        # VMEM, 2 MiB at 4,096. One buffer each instead of two keeps the
        # program under the 16 MiB whatever XLA fuses into its operands
        # (two buffers: 16.2-16.8 MB inside a step, PR 28).
        once = lambda width: pl.BlockSpec((1, seq, width), lambda b, *_: (b, 0, 0),
                                          pipeline_mode=pl.Buffered(1))
        whole, stats = once, once(1)
    with jax.named_scope(plan.scope):
        return pl.pallas_call(
            functools.partial(
                _bwd_kernel, sm_scale=sm_scale, causal=causal,
                tile_q=plan.tile_q, tile_k=plan.tile_k, static=plan.unrolled,
            ),
            grid=(bh, *tiles),
            in_specs=[whole(d), mine(d), mine(dv), whole(dv), stats, stats],
            # In the loop form dq is revisited by every K tile of a head (its
            # index map ignores the tile) and written back when the grid moves on.
            out_specs=[head(d), mine(d), mine(dv)],
            out_shape=[jax.ShapeDtypeStruct((bh, seq, width), q.dtype) for width in (d, d, dv)],
            scratch_shapes=[pltpu.VMEM((seq, d), jnp.float32)],
            interpret=interpret,
            name="flash_bwd",
            compiler_params=_compiler_params(interpret, "parallel", *["arbitrary"] * len(tiles)),
        )(q, k, v, do, lse, delta)


# --------------------------------------------------------------------------- blockwise (long-seq XLA)
def blockwise_attention(q, k, v, causal=True, sm_scale: Optional[float] = None,
                        block_k: int = 1024):
    """O(S * block_k)-memory attention as a remat'ed scan over K blocks — the
    long-sequence path while the forward kernel keeps full-seq K/V in VMEM
    (which caps it at `MAX_HEAD_BYTES` a head). Exact, differentiable, pure XLA.
    `causal`: True, False or a mask by structure, as `xla_attention` takes it."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    B, H, S, D = q.shape
    if S % block_k:
        return xla_attention(q, k, v, causal=causal, sm_scale=sm_scale)
    nblk = S // block_k
    kb = jnp.moveaxis(k.reshape(B, H, nblk, block_k, D), 2, 0)  # (nblk, B, H, bk, D)
    vb = jnp.moveaxis(v.reshape(B, H, nblk, block_k, D), 2, 0)
    qf = q.astype(jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, (S, block_k), 0)

    @jax.checkpoint
    def body(carry, inp):
        m_prev, l_prev, acc = carry
        kblk, vblk, j = inp
        s = jnp.einsum(
            "bhqd,bhkd->bhqk", qf, kblk.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        ) * sm_scale
        if causal is not False:
            col = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (S, block_k), 1)
            s = jnp.where((row >= col if causal is True else causal.kept(row, col))[None, None], s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vblk.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)
    acc0 = jnp.zeros((B, H, S, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), (kb, vb, jnp.arange(nblk)))
    l = jnp.maximum(l, 1e-30)
    return (acc / l[..., None]).astype(q.dtype)


# --------------------------------------------------------------------------- public entry
# Both `custom_vjp`s flatten inside their rules, not round them: the residuals are then the caller's own arrays, and
# a layer scan stacks `o` once (the module's docstring says what a reshape outside cost).
def _flat(x):
    """(batch, heads, seq, d) as the kernels take it, (batch * heads, seq, d): a bitcast."""
    return x.reshape(-1, *x.shape[2:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_bhsd(q, k, v, causal, sm_scale, plan, interpret):
    return _flash_fwd_rule(q, k, v, causal, sm_scale, plan, interpret)[0]


def _flash_fwd_rule(q, k, v, causal, sm_scale, plan, interpret):
    o, lse = _fwd(_flat(q), _flat(k), _flat(v), causal, sm_scale, plan, interpret)
    o = o.reshape(*q.shape[:-1], v.shape[-1])
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(causal, sm_scale, plan, interpret, res, g):
    q, k, v, o, lse = res
    dq, dk, dv = _bwd(causal, sm_scale, plan, interpret, (_flat(q), _flat(k), _flat(v), _flat(o), lse), _flat(g))
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


_flash_bhsd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_pairs(q, k, v, keep, causal, sm_scale, plan, interpret):
    """Both passes a (Q tile, K tile) pair a program: (o, lse) of q (batch,
    heads, seq, d) on k, v (batch, kv heads, seq, d) under `keep` (batch, seq,
    spans * 128; None: every key). `lse` (batch * heads, seq, 1) carries no
    gradient: it is for whoever needs the probabilities again."""
    return _flash_pairs_fwd(q, k, v, keep, causal, sm_scale, plan, interpret)[0]


def _flash_pairs_fwd(q, k, v, keep, causal, sm_scale, plan, interpret):
    o, lse = _fwd_pairs(_flat(q), _flat(k), _flat(v), keep, causal, sm_scale, plan, interpret)
    o = o.reshape(q.shape)
    return (o, lse), (q, k, v, keep, o, lse)


def _flash_pairs_bwd(causal, sm_scale, plan, interpret, res, g):
    q, k, v, keep, o, lse = res
    do, o = _flat(g[0]), _flat(o)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)[..., None]
    dq, dk, dv = _bwd_pairs(_flat(q), _flat(k), _flat(v), do, lse, delta, causal, sm_scale, plan, interpret, keep)
    group = q.shape[1] // k.shape[1]
    if group > 1:  # a key/value head's gradient is its query heads' sum
        dk, dv = (x.reshape(-1, group, *x.shape[1:]).sum(axis=1, dtype=jnp.float32).astype(x.dtype)
                  for x in (dk, dv))
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape), None


_flash_pairs.defvjp(_flash_pairs_fwd, _flash_pairs_bwd)


class KernelPlan(NamedTuple):
    """The tile schedule one (batch, head) program of both kernels walks."""

    tile_q: int
    tile_k: int
    tiles_visited: int  # tile pairs computed: on or under the diagonal when causal
    tiles_masked: int  # of those, the ones the diagonal crosses (they pay the mask)
    tiles_total: int  # the whole square
    unrolled: bool  # straight-line code with static slices, else fori_loops

    @property
    def scope(self) -> str:
        """The `jax.named_scope` round each `pallas_call`, so a kernel event's
        `op_name` in a trace says which schedule it ran."""
        return f"tiles_{self.tiles_visited}of{self.tiles_total}"


def _kernel_blocks(seq: int, head_dim: int, causal,
                   block_q: Optional[int] = None, block_k: Optional[int] = None,
                   itemsize: int = 2, pairs: bool = False) -> KernelPlan:
    """The one place tile sizes and the form of the schedule are chosen, from
    what the call observes: `seq`, `head_dim`, `causal` (True, False or a mask
    by structure, which always runs a pair a program), and `block_q` /
    `block_k` where the caller passes them. Tiles are capped to seq and shrunk
    to a divisor (gcd keeps the largest power-of-two factor), so a default
    works for any seq that has one: S=1536 walks 3 x 3 tiles of 512. `pairs`:
    the call runs both passes a pair a program (`_streams_pairs`), which is
    never unrolled and walks `LONG_HEAD_TILE` x `PAIRS_TILE_K` pairs."""

    def plan(default):
        tile_q = math.gcd(min(block_q or default, seq), seq)
        wide_k = PAIRS_TILE_K if pairs and head_dim <= LANES else default
        tile_k = math.gcd(min(block_k or wide_k, seq), seq)
        if pairs:  # a K tile is whole bits of one span of a packed selection (`_keep_tile`)
            tile_k = math.gcd(tile_k, KEEP_SPAN)
        n_q, n_k = seq // tile_q, seq // tile_k
        visited = masked = 0
        for i in range(n_q):
            tiles = _tiles_under(causal, i, tile_q, tile_k, n_k, True)
            visited += len(tiles)
            masked += sum(crossed for _, crossed in tiles)
        unrolled = (causal is True and not pairs and max(tile_q, tile_k) <= CAUSAL_TILE
                    and visited <= MAX_UNROLLED_TILES
                    and visited * tile_q * tile_k <= MAX_UNROLLED_SCORES
                    and seq * head_dim * itemsize <= MAX_UNROLLED_HEAD_BYTES)
        return KernelPlan(tile_q, tile_k, visited, masked, n_q * n_k, unrolled)

    assert pairs or isinstance(causal, bool), f"{causal} runs a pair a program alone (`_streams_pairs`)"
    if causal is True:
        small = plan(CAUSAL_TILE)
        if small.unrolled:
            return small
    small = pairs or _long_head(seq, head_dim, itemsize) or _streamed_head(seq, head_dim, itemsize)
    return plan(LONG_HEAD_TILE if small else FULL_TILE)


def _streams_pairs(seq: int, head_dim: int, itemsize: int, kv_heads_fewer: bool, keep: bool, causal=True) -> bool:
    """Whether a call runs both passes a (Q tile, K tile) pair a program
    (`_flash_pairs`): a head the forward program cannot hold, a selection,
    key/value heads shared by a group of query heads, or a mask other than the
    causal diagonal (the whole-head forms know that one alone)."""
    return (keep or kv_heads_fewer or not isinstance(causal, bool)
            or _head_bytes(seq, head_dim, itemsize) > MAX_HEAD_BYTES)


def kernel_plan(shape, causal=True,
                block_q: Optional[int] = None, block_k: Optional[int] = None,
                dtype=jnp.bfloat16, kv_heads: Optional[int] = None, keep: bool = False) -> KernelPlan:
    """The schedule the kernels run for q/k/v of `shape` (batch, heads, seq,
    head_dim), `kv_heads` key/value heads where they are fewer, with a
    selection where `keep`: static, so asking costs nothing per step."""
    _, h, s, d = shape
    itemsize = jnp.dtype(dtype).itemsize
    pairs = _streams_pairs(s, d, itemsize, kv_heads not in (None, h), keep, causal)
    return _kernel_blocks(s, d, causal, block_q, block_k, itemsize, pairs)


def select_backend(shape, platform: Optional[str] = None,
                   block_q: Optional[int] = None, block_k: Optional[int] = None) -> str:
    """The implementation `flash_attention(backend=None)` runs for q/k/v of
    `shape` (batch, heads, seq, head_dim): "pallas" | "blockwise" | "xla".

    The choice is by platform and shape only, and this function is the one
    place it is made, so a caller can say which path its step compiled
    without reading the HLO: off-TPU the XLA form; on TPU the kernel while
    the forward program holds K and V of one (batch, head) in VMEM twice over
    beside its tiles: a head of up to `MAX_HEAD_BYTES` there, its last
    dimension padded to the 128 lanes (in bf16 8,192 positions at a head_dim
    of 64 or 128, 4,096 at 256); beyond that the kernel with K and V streamed
    (`_fwd_pairs`) up to `MAX_STREAMED_HEAD_BYTES` (16,384 positions at 64 or
    128, 8,192 at 256), the blockwise scan for what no form holds, and the
    XLA form for a sequence with no block of at least 128 dividing it. The
    backward program's form follows from the same count (`_bwd`): whole heads
    up to `LONG_HEAD_BYTES`, (Q tile, K tile) pairs above.
    """
    if (platform or jax.default_backend()) != "tpu":
        return "xla"
    _, _, s, d = shape
    if _head_bytes(s, d, 2) > MAX_STREAMED_HEAD_BYTES:
        return "blockwise"
    plan = kernel_plan(shape, True, block_q, block_k)
    if min(plan.tile_q, plan.tile_k) < 128:
        return "xla"
    return "pallas"


def flash_attention(
    q,
    k,
    v,
    causal=True,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    backend: Optional[str] = None,
    interpret: bool = False,
    mesh=None,
    keep=None,
    return_lse: bool = False,
):
    """Multi-head attention, (batch, heads, seq, head_dim) layout; k and v may
    hold fewer heads, each shared by a group of consecutive query heads. v's
    last dimension may be another than q's and k's (latent attention with keys
    of 192 and values of 128): o then has v's, in the whole-head forward and
    both backward forms; the plan and the default `sm_scale` are by q's.

    causal: True (the diagonal), False (every key), or a mask by structure, a
      static hashable description (`BlockDiffusion`, `SlidingWindow`): the kernels' schedules
      skip the tile pairs it leaves empty and make a crossed pair's mask from
      iotas, so it costs no operand.
    keep: (batch, seq, spans * 128) int32, a selection of keys for every query,
      shared by the heads and packed a bit a pair (`pack_keep`); with `causal`
      a key is attended to where both allow it. None (every key) runs the
      programs this function ran before it took the argument.
    return_lse: also each row's log-sum-exp over the keys it attends to,
      (batch, heads, seq) f32, with no gradient.
    backend: "pallas" | "xla" | "blockwise" | None (`select_backend`).
    block_q, block_k: the kernel's tile sizes; None lets `kernel_plan` pick
      them from the shape.
    mesh: the jax.sharding.Mesh the surrounding jit shards over. XLA cannot
      partition a Mosaic call by itself, so on more than one device the
      kernel runs inside a shard_map with batch over (data, fsdp) and heads
      over tensor — attention is independent per (batch, head), so no
      collective is needed and each device runs the kernel on its own block.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if backend is None:
        # The platform the computation is compiled for: the mesh's when there
        # is one (also true when compiling ahead of time for a chip this
        # process does not hold), else this process's default.
        platform = mesh.devices.flat[0].platform if mesh is not None else None
        backend = select_backend(q.shape, platform, block_q, block_k)
    if backend == "xla":
        return xla_attention(q, k, v, causal=causal, sm_scale=sm_scale, keep=keep, return_lse=return_lse)
    if backend == "blockwise":
        if keep is not None or return_lse:
            raise NotImplementedError(
                f"flash_attention: a head of {q.shape[2]} x {q.shape[3]} runs the blockwise scan, "
                "which takes no selection and returns no row statistics")
        return blockwise_attention(q, *_repeat_kv(q, k, v), causal=causal, sm_scale=sm_scale)
    pairs = return_lse or _streams_pairs(
        q.shape[2], q.shape[3], q.dtype.itemsize, k.shape[1] != q.shape[1], keep is not None, causal)
    plan = _kernel_blocks(q.shape[2], q.shape[3], causal, block_q, block_k, q.dtype.itemsize, pairs)
    if min(plan.tile_q, plan.tile_k) < 128:
        raise ValueError(
            f"flash_attention(backend='pallas'): seq_len {q.shape[2]} has no "
            "block of at least 128 dividing it; the kernel cannot tile it"
        )
    if pairs:
        if v.shape[-1] != q.shape[-1]:
            raise NotImplementedError(
                f"flash_attention: values {v.shape[-1]} wide under keys of {q.shape[-1]} where the forward pass "
                "runs a pair a program (a selection, grouped heads, a mask by structure, a head past "
                "`MAX_HEAD_BYTES`): that form holds one width")
        return _pairs_call(q, k, v, keep, causal, sm_scale, plan, interpret, mesh, return_lse)

    def kernel(q, k, v):
        return _flash_bhsd(q, k, v, causal, sm_scale, plan, interpret)

    if mesh is not None and mesh.size > 1:
        from ray_tpu.parallel import ShardingRules

        # The rules drop a mesh axis that does not divide its dimension
        # (12 heads on tensor=8 stay replicated), as they do for parameters.
        spec = ShardingRules().mesh_axes(
            ("batch", "heads", None, None), mesh=mesh, shape=q.shape
        )
        kernel = jax.shard_map(
            kernel, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )
    return kernel(q, k, v)


def _pairs_call(q, k, v, keep, causal, sm_scale, plan, interpret, mesh, return_lse):
    """`_flash_pairs` on (batch, heads, seq, d) operands, over `mesh` as
    `flash_attention` partitions its other kernels (a selection goes with its
    row's batch)."""
    def kernel(q, k, v, *keep):
        o, lse = _flash_pairs(q, k, v, keep[0] if keep else None, causal, sm_scale, plan, interpret)
        return o, jax.lax.stop_gradient(lse).reshape(q.shape[:3])

    operands = (q, k, v) + (() if keep is None else (keep,))
    if mesh is not None and mesh.size > 1:
        from ray_tpu.parallel import ShardingRules

        rules = ShardingRules()
        spec = rules.mesh_axes(("batch", "heads", None, None), mesh=mesh, shape=q.shape)
        kv_spec = rules.mesh_axes(("batch", "heads", None, None), mesh=mesh, shape=k.shape)
        if kv_spec != spec:
            raise NotImplementedError("key/value heads that the mesh cannot split as it splits the query heads")
        in_specs = (spec, spec, spec) + (() if keep is None else (
            rules.mesh_axes(("batch", None, None), mesh=mesh, shape=keep.shape),))
        kernel = jax.shard_map(kernel, mesh=mesh, in_specs=in_specs,
                               out_specs=(spec, jax.sharding.PartitionSpec(*spec[:3])), check_vma=False)
    o, lse = kernel(*operands)
    return (o, lse) if return_lse else o
