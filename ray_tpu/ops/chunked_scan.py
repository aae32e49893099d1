"""The walk of a chunked recurrent scan, once for every rule that has one (`ops/gated_delta_rule.py`, `ops/kda.py`,
`ops/ssd.py`): a recurrence whose state is a matrix (d_k, d_v) a head, rewritten over chunks of C positions as matrix
products, with the state a chunk hands to the next in VMEM scratch.

A rule (`Rule`) brings the mathematics of one chunk of one head on plain two-dimensional arrays, three functions of
its own module: `chunk_gates(k, gam, beta)`, all that stands before the inverse of `I + A` (a dict with `a`, the
strictly lower triangular `A`, among whatever the other two read); `chunk_fwd(q, k, v, gam, beta, s, first=None)` ->
(o, the state after the chunk); `chunk_bwd(q, k, v, gam, beta, s, do, ds_new, first=None)` -> (dq, dk, dv, dgam,
dbeta, ds), where `first` is `chunk_gates`' dict with the inverse `t` added. `gam` is the running sum of the log decay
inside the chunk, `beta` a row (1, C) (or None, and `dbeta` None with it). This module does the rest, the same for
every rule:

The XLA form (`_xla_form`) maps `chunk_fwd` over batch and heads inside a `lax.scan` over the chunks: what runs off
the TPU, differentiated by jax, and what the kernels are held to. The Mosaic kernels `<rule>_fwd` and `<rule>_bwd`
call the same functions on their blocks, the chunks along a sequential grid axis with the state (`dS` in the
reverse walk, which starts from the row's end) in VMEM scratch. The forward kernel writes out the state every chunk
starts from, (B, H, S / C, d_k, d_v) f32, for the backward pass, which makes `T` and `N` again.

What a rule may leave out. The inverse: a rule with no solve inside a chunk (a plain decayed outer-product write,
`ops/ssd.py`) says `inverse=False`; its `chunk_gates` brings no `a`, the walk makes no `T` and `first` has no `t`.
`beta`: a call without one (`chunked_scan(..., beta=None)`) hands the three functions `None` in its place, pads a row
with zero values (no write), and the kernels have neither the operand nor its gradient.

A group's q and k. Where q and k hold fewer heads than v, (B, H / n, S, d_k) beside (B, H, S, d_v), each is shared by
the n consecutive heads of its group, and no copy a head is made: a program walks G heads of one group (G divides n)
and its q and k blocks are the group's, found by the block's index map. The grid is then (groups, chunks, programs a
group) with the last axis innermost, every head of the group keeps its state in scratch between its chunks, and in
the reverse walk dq and dk are summed over the group's heads in f32 scratch and written once a chunk. The XLA form
repeats q and k a head (small arrays, off the TPU) and jax sums their gradients.

A program walks G heads, unrolled in one body (`heads_per_program`: a divisor of the heads the call holds, by the
VMEM they need; the kernels' scope says which, `chunk_128/heads_3of30`). The doubling that makes `T`
(`_unit_lower_inverses`) is twelve products of C^3 each waiting for the one before it, and a head alone has nothing
to issue while one drains; heads share nothing, so G of them are G independent chains, made level by level side by
side: Mosaic overlaps products that stand next to each other in the program, and does not lift a later head's over an
earlier head's chain (heads unrolled one after the other gained nothing).

The MXU passes of a product follow its operands' types as they arrive (`_mm`): six for two f32 arrays, three where
one is bf16, one where both are.

The call (`chunked_scan`) checks the chunk, picks the backend by the platform the computation is compiled for, pads a
row to a whole number of chunks (beta 0, g 0: no write, no decay), makes the running sum, flattens batch and heads,
and on more than one device runs the kernels inside a `shard_map` (XLA cannot partition a Mosaic call): batch over
(data, fsdp), heads over tensor, as `flash_attention(mesh=)`; heads and rows are independent.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Positions a chunk; a row is padded to a whole number of them. On the v5e a layer-row of the Olmo-Hybrid cell takes
# 12.0 ms forward and backward at 128, 13.7 at 64, 18.2 at 32 (`tools/gdn_bench.py`, PR 51), and the states kept for the
# backward pass halve with each doubling.
CHUNK = 128
F32, BF16 = jnp.float32, jnp.bfloat16
NT = (((1,), (1,)), ((), ()))  # a @ b.T
NN = (((1,), (0,)), ((), ()))  # a @ b
TN = (((0,), (0,)), ((), ()))  # a.T @ b


class Rule(NamedTuple):
    """What a recurrence brings to the walk: a value its module makes once."""
    name: str  # the public entry point's, in the errors' text
    kernels: str  # the Mosaic kernels are `<kernels>_fwd` and `<kernels>_bwd`
    # The log decay's layout. False: a number a head and position, g (B, H, S); a kernel's block of it is a whole row
    # a head, (G, S / C, C), and a chunk's `gam` the row (1, C) at its place. True: a vector of d_k, g (B, H, S, d_k);
    # the block is the chunk's own (G, C, d_k), as k's. Everything outside the kernels (the padding, the running
    # sum, the shard_map's specs) follows the positions on axis 2 whatever comes after them.
    gate_a_channel: bool
    # () -> (chunk_gates, chunk_fwd, chunk_bwd): read from the rule's module every time the walk is traced, so that a
    # fault planted there (a test's, a readings tool's) is the mathematics the walk runs.
    functions: Callable
    chunk_flops: Callable  # (chunk, dk, dv, dtype, backward) -> FLOP of one chunk of one head: what XLA is told
    transcendentals: Callable  # (chunk, dk) -> exponentials of one chunk of one head, the same
    itemsize: Optional[int] = None  # the item size `heads_per_program` is asked at; None: the keys' own
    # False: no solve inside a chunk. `chunk_gates`' dict has no `a`, the walk makes no inverse and `first` no `t`.
    inverse: bool = True
    max_heads: Optional[int] = None  # heads a program at most; None: `MAX_HEADS`, what the inverse's chain asks for


def _bf16_parts(x):
    """An f32 array as three bf16 arrays whose sum (in f32) is the array to 2^-24 of it: its high, middle
    and low parts, what the MXU's six-pass product makes of an f32 operand."""
    high = x.astype(BF16)
    rest = x - high.astype(F32)
    middle = rest.astype(BF16)
    return high, middle, (rest - middle.astype(F32)).astype(BF16)


def _mm(a, b, dims=NN):
    """a . b with f32 accumulation, by the operands' types as they arrive. Both f32: at full f32 precision,
    the MXU's six passes (an operand is three bf16 parts; the six products of parts that matter). One
    bf16, the other f32: three passes, the bf16 operand against the three parts of the f32 one, which
    is the six-pass product of its cast to f32 with the passes that multiply zeros left out. Both
    bf16 (or any other pair): one product of the operands as they come."""
    one = functools.partial(jax.lax.dot_general, dimension_numbers=dims, preferred_element_type=F32)
    if a.dtype == F32 and b.dtype == F32:
        return one(a, b, precision=jax.lax.Precision.HIGHEST)
    if a.dtype == BF16 and b.dtype == F32:
        high, middle, low = (one(a, part) for part in _bf16_parts(b))
        return high + (middle + low)
    if a.dtype == F32 and b.dtype == BF16:
        high, middle, low = (one(part, b) for part in _bf16_parts(a))
        return high + (middle + low)
    return one(a, b)


def _iotas(n: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0), jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))


def _col(row):
    """(1, C) -> (C, 1) by the diagonal of its broadcast: no transpose of a one-row tile."""
    r, c = _iotas(row.shape[1])
    return jnp.sum(jnp.where(r == c, row, 0.0), axis=1, keepdims=True)


def _row(col):
    r, c = _iotas(col.shape[0])
    return jnp.sum(jnp.where(r == c, col, 0.0), axis=0, keepdims=True)


def _unit_lower_inverses(mats):
    """(I + a)^-1 for each strictly lower triangular (C, C) `a` of `mats`, C a power of two, by doubling
    the block size of the block diagonal's inverse: that at size 2b is `X - X E X`, `X` that at size b and `E` the
    entries of `a` that join a block's halves, two C x C products a level above the first and no row-by-row
    substitution. A level's two products for every matrix before the next level's, so that each product stands
    beside the other matrices' and not behind its own last one."""
    n = mats[0].shape[0]
    r, c = _iotas(n)
    joins = lambda a, level: jnp.where(((r ^ c) >> level) == 1, a, 0.0)  # noqa: E731  (same block of 2b, other half of it)
    xs = [(r == c).astype(F32) - joins(a, 0) for a in mats]  # blocks of 2: X - X E X with X the identity
    level = 1
    while (1 << level) < n:
        ys = [_mm(joins(a, level), x) for a, x in zip(mats, xs)]
        xs = [x - _mm(x, y) for x, y in zip(xs, ys)]
        level += 1
    return xs


# --------------------------------------------------------------------------- the XLA form
def _chunked(x, chunk: int):
    """(B, H, S, ...) -> (S / C, B, H, C, ...): the chunks first, for a scan."""
    b, h, s = x.shape[:3]
    x = x.reshape(b, h, s // chunk, chunk, *x.shape[3:])
    return jnp.moveaxis(x, 2, 0)


def _xla_form(rule: Rule, q, k, v, gam, beta, chunk: int):
    """The chunked form on whole arrays: q, k (B, H, S, d_k), v (B, H, S, d_v), `gam` (B, H, S[, d_k]) and `beta`
    (B, H, S) f32 or None, S a whole number of chunks. A group's q and k (fewer heads than v) are repeated a head."""
    over_heads = jax.vmap(jax.vmap(rule.functions()[1]))
    share = v.shape[1] // k.shape[1]
    if share > 1:
        q, k = (jnp.repeat(x, share, axis=1) for x in (q, k))

    def one_chunk(s, xs):
        qc, kc, vc, gc, bc = xs
        o, s = over_heads(qc, kc, vc, gc if rule.gate_a_channel else gc[:, :, None],
                          None if bc is None else bc[:, :, None], s)
        return s, o

    b, h, _, dk = k.shape
    s0 = jnp.zeros((b, h, dk, v.shape[-1]), F32)
    _, o = jax.lax.scan(one_chunk, s0, tuple(None if x is None else _chunked(x, chunk) for x in (q, k, v, gam, beta)))
    return jnp.moveaxis(o, 0, 2).reshape(v.shape).astype(v.dtype)


# --------------------------------------------------------------------------- the kernels
# `f` as the kernels call it. A kernel's body is traced for every call in a step and every time the step is traced (36
# times in the Olmo-Hybrid cell's set-up), the program's heads unrolled in it; under `jax.jit` the chunk's mathematics
# is a jaxpr kept by function and operand types, which Python walks once a process (`compile.trace_s` 48.3 -> 13.8 s
# there, PR 52), and the lowering writes it in line where it is called.
_once = jax.jit


def _gate_at(rule: Rule, h: int, at):
    """Where head h's gate of chunk `at` lies in a kernel's block of them: to read or to write."""
    return (h,) if rule.gate_a_channel else (h, pl.ds(at, 1), slice(None))


def _heads_of_a_program(rule: Rule, k_ref, gam_ref, beta_ref, at, heads: int):
    """[(k, gam, beta, `chunk_gates`' parts and, where the rule has one, the inverse `t`)] of chunk `at`, one a head
    of the program's `heads`; k is the head's own row of the block, or its group's where the block holds fewer. The
    heads share nothing past their keys, so their doublings are independent chains: made together, level by level."""
    of = lambda h: h * k_ref.shape[0] // heads  # noqa: E731
    heads = [(k_ref[of(h)], gam_ref[_gate_at(rule, h, at)], None if beta_ref is None else beta_ref[h, pl.ds(at, 1), :])
             for h in range(heads)]
    firsts = [_once(rule.functions()[0])(*head) for head in heads]
    if rule.inverse:
        for first, t in zip(firsts, _once(_unit_lower_inverses)([first["a"] for first in firsts])):
            first["t"] = t
    return [(*head, first) for head, first in zip(heads, firsts)]


def _slots(share: int, g: int):
    """Where a program's g heads keep their state in scratch: the scratch is the program's own (`share` 1: a head a
    q and k), or the group's `share` heads' and this program's run of it (the grid's last axis counts the runs)."""
    if share == 1:
        return slice(None), lambda h: h
    first = pl.program_id(2) * g
    return pl.ds(first, g), lambda h: first + h


def _fwd_kernel(rule: Rule, share: int, q_ref, k_ref, v_ref, gam_ref, *rest):
    *beta_ref, o_ref, states_ref, s_ref = rest
    i, g = pl.program_id(1), v_ref.shape[0]
    mine, slot = _slots(share, g)

    @pl.when(i == 0)
    def _():
        s_ref[mine] = jnp.zeros((g, *s_ref.shape[1:]), F32)

    walked = _heads_of_a_program(rule, k_ref, gam_ref, beta_ref[0] if beta_ref else None, i, g)
    for h, (k, gam, beta, first) in enumerate(walked):
        s = s_ref[slot(h)]
        states_ref[h, 0] = s
        o, s_new = _once(rule.functions()[1])(q_ref[h * q_ref.shape[0] // g], k, v_ref[h], gam, beta, s, first)
        o_ref[h] = o.astype(o_ref.dtype)
        s_ref[slot(h)] = s_new


def _bwd_kernel(rule: Rule, share: int, has_beta: bool, q_ref, k_ref, v_ref, gam_ref, *rest):
    rest = list(rest)
    beta_ref = rest.pop(0) if has_beta else None
    states_ref, do_ref, dq_ref, dk_ref, dv_ref, dgam_ref = rest[:6]
    dbeta_ref = rest[6] if has_beta else None
    ds_ref, *sums_ref = rest[6 + has_beta:]
    i, g = pl.program_id(1), v_ref.shape[0]
    at = pl.num_programs(1) - 1 - i  # the chunk: the walk is from the row's end
    mine, slot = _slots(share, g)

    @pl.when(i == 0)
    def _():
        ds_ref[mine] = jnp.zeros((g, *ds_ref.shape[1:]), F32)

    of_group = []  # (dq, dk) of every head of the program, where they are a group's to add up
    for h, (k, gam, beta, first) in enumerate(_heads_of_a_program(rule, k_ref, gam_ref, beta_ref, at, g)):
        dq, dk, dv, dgam, dbeta, ds = _once(rule.functions()[2])(
            q_ref[h * q_ref.shape[0] // g], k, v_ref[h], gam, beta, states_ref[h, 0], do_ref[h], ds_ref[slot(h)], first)
        if share == 1:
            dq_ref[h] = dq.astype(dq_ref.dtype)
            dk_ref[h] = dk.astype(dk_ref.dtype)
        else:
            of_group.append((dq, dk))
        dv_ref[h] = dv.astype(dv_ref.dtype)
        dgam_ref[_gate_at(rule, h, at)] = dgam
        if has_beta:
            dbeta_ref[h, pl.ds(at, 1), :] = dbeta
        ds_ref[slot(h)] = ds
    if share > 1:  # dq, dk of the group's chunk: summed over its programs in f32, written by the last
        (sums,), run = sums_ref, pl.program_id(2)
        dq, dk = (functools.reduce(jnp.add, parts) for parts in zip(*of_group))

        @pl.when(run == 0)
        def _():
            sums[0], sums[1] = dq, dk

        @pl.when(run > 0)
        def _():
            sums[0] += dq
            sums[1] += dk

        @pl.when(run == pl.num_programs(2) - 1)
        def _():
            dq_ref[0] = sums[0].astype(dq_ref.dtype)
            dk_ref[0] = sums[1].astype(dk_ref.dtype)


def _lanes(d: int) -> int:
    return -(-d // 128) * 128


# What a program's heads may hold of VMEM between them: three quarters of the 16 MiB Mosaic gives a kernel on the v5e.
VMEM_BUDGET = 12 << 20
# Heads a program at most. On the v5e a layer-row of the Olmo-Hybrid cell (30 heads x 4,096) takes, forward + backward,
# 10.67 ms at one head a program, 7.65 at two, 7.28 at three, 7.20 at five, 7.05 at six, and the two kernels compile in
# 0.8, 1.6, 3.5, 6.5, 7.9 s (`tools/gdn_bench.py --heads`, PR 52): past three the doubling is within 16 % of its six-pass
# floor and a further head buys 1 % for twice the compile.
MAX_HEADS = 3


def heads_per_program(heads: int, seq: int, chunk: int, dk: int, dv: int, itemsize: int, share: int = 1,
                      most: Optional[int] = None) -> int:
    """G, the heads one program walks side by side: the largest divisor of the `heads` the call holds (batch x
    heads on this device), at most `most` (`MAX_HEADS`), whose blocks, scratch and working set in the backward kernel
    (the larger one) fit `VMEM_BUDGET`; 1 where nothing divides them. Counted for a gate a row a head; a rule whose gate
    is as wide as the keys asks at f32 items (`Rule.itemsize`). With `share` heads on one q and k, G divides `share`
    (a program's heads are of one group) and what the group keeps in scratch for all its heads comes off the budget."""
    n = seq // chunk
    # q, k, dq, dk; v, do, dv; the chunk's state; the two gates and their gradients, a whole row of them a head
    blocks = (4 * chunk * _lanes(dk) + 3 * chunk * _lanes(dv)) * itemsize + dk * _lanes(dv) * 4 + 4 * n * chunk * 4
    working = (8 * chunk * _lanes(chunk) + 4 * chunk * (_lanes(dk) + _lanes(dv))) * 4  # f32 values live at once
    a_head = 2 * blocks + dk * _lanes(dv) * 4 + working  # every block has two buffers; dS in scratch
    room = VMEM_BUDGET
    if share > 1:  # every head's dS and the group's sums of dq and dk
        heads, room = share, room - (share * dk * _lanes(dv) + 2 * chunk * _lanes(dk)) * 4
    fit = max(1, min(most or MAX_HEADS, room // a_head))
    return max(g for g in range(1, fit + 1) if heads % g == 0)


def plan(rule: Rule, k, v, chunk: int):
    """(G, the scopes that name the plan: `chunk_128`, `heads_3of30` and, where `share` heads read one q and k,
    `group_<share>`) for flat heads k (BH / share, S, d_k), v (BH, S, d_v)."""
    (bh, seq, dv), dk = v.shape, k.shape[-1]
    share = bh // k.shape[0]
    g = heads_per_program(bh, seq, chunk, dk, dv, rule.itemsize or k.dtype.itemsize, share, rule.max_heads)
    return (g, f"chunk_{chunk}", f"heads_{g}of{bh}") + ((f"group_{share}",) if share > 1 else ())


def _call(rule: Rule, backward: bool, operands, chunk: int, interpret: bool):
    """One of the two `pallas_call`s on flat heads. Forward: `operands` (q, k, v, gam, beta), q, k (BH / share, S,
    d_k), v (BH, S, d_v), gam (BH, S[, d_k]) and beta (BH, S) f32 or None -> (o, the state every chunk starts from
    (BH, S / C, d_k, d_v) f32). The reverse walk: (q, k, v, gam, beta, states, do) -> the five gradients, each laid out
    like what it is the gradient of (None for no beta). The grid is (programs of G heads, chunks), the second axis
    sequential: chunk i forward, chunk n - 1 - i in the reverse walk; with `share` > 1 heads on one q and k it is
    (groups, chunks, programs a group), the last two sequential."""
    q, k, v, gam, beta = operands[:5]
    (bh, seq, dv), dk = v.shape, k.shape[-1]
    n, share = seq // chunk, bh // k.shape[0]
    g, *scopes = plan(rule, k, v, chunk)
    at = (lambda i: n - 1 - i) if backward else (lambda i: i)
    if share == 1:
        grid, head_at, keys_at = (bh // g, n), (lambda h, i: h), (g, lambda h, i: h)
    else:
        runs = share // g
        grid, head_at, keys_at = (bh // share, n, runs), (lambda p, i, j: p * runs + j), (1, lambda p, i, j: p)
    per_chunk = lambda d, where=(g, head_at): pl.BlockSpec(  # noqa: E731
        (where[0], chunk, d), lambda *ids: (where[1](*ids), at(ids[1]), 0))
    per_head = pl.BlockSpec((g, n, chunk), lambda *ids: (head_at(*ids), 0, 0))
    states = pl.BlockSpec((g, 1, dk, dv), lambda *ids: (head_at(*ids), at(ids[1]), 0, 0))
    by_chunk = lambda x: x.reshape(bh, n, chunk)  # noqa: E731  (a row of gates a head, a chunk a sublane row)
    if rule.gate_a_channel:
        gate, gate_width, gates = per_chunk(dk), dk, [gam]
    else:
        gate, gate_width, gates = per_head, 1, [by_chunk(gam)]
    qkv_gates = [per_chunk(dk, keys_at), per_chunk(dk, keys_at), per_chunk(dv), gate]
    if beta is not None:
        gates, qkv_gates = gates + [by_chunk(beta)], qkv_gates + [per_head]
    like = lambda x, dtype=None: jax.ShapeDtypeStruct(x.shape, dtype or x.dtype)  # noqa: E731
    passes = 2 if backward else 1  # over q, k, v, o and over the gates: read, and in the reverse walk written too
    kernel = (functools.partial(_bwd_kernel, rule, share, beta is not None) if backward
              else functools.partial(_fwd_kernel, rule, share))
    scratch = [pltpu.VMEM((g if share == 1 else share, dk, dv), F32)]
    if backward and share > 1:
        scratch.append(pltpu.VMEM((2, chunk, dk), F32))
    with contextlib.ExitStack() as named:
        for scope in scopes:
            named.enter_context(jax.named_scope(scope))
        out = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=qkv_gates + ([states, per_chunk(dv)] if backward else []),
            out_specs=qkv_gates if backward else [per_chunk(dv), states],
            out_shape=([like(q), like(k), like(v)] + [like(x, F32) for x in gates] if backward
                       else [like(v), jax.ShapeDtypeStruct((bh, n, dk, dv), F32)]),
            scratch_shapes=scratch,
            interpret=interpret,
            name=rule.kernels + ("_bwd" if backward else "_fwd"),
            compiler_params=None if interpret else pltpu.CompilerParams(
                dimension_semantics=("parallel",) + ("arbitrary",) * (len(grid) - 1)),
            cost_estimate=pl.CostEstimate(
                flops=bh * n * rule.chunk_flops(chunk, dk, dv, k.dtype, backward),
                bytes_accessed=(seq * passes * (2 * dk * (bh // share) + 2 * dv * bh) * q.dtype.itemsize
                                + bh * (n * dk * dv * 4 + passes * seq * (gate_width + len(gates) - 1) * 4)),
                transcendentals=bh * n * rule.transcendentals(chunk, dk)),
        )(q, k, v, *gates, *operands[5:])
    if not backward:
        return out
    return (*out[:3], out[3].reshape(gam.shape), None if beta is None else out[4].reshape(beta.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 6, 7))
def _kernels(rule, q, k, v, gam, beta, chunk, interpret):
    return _call(rule, False, (q, k, v, gam, beta), chunk, interpret)[0]


def _kernels_fwd(rule, q, k, v, gam, beta, chunk, interpret):
    o, states = _call(rule, False, (q, k, v, gam, beta), chunk, interpret)
    return o, (q, k, v, gam, beta, states)


def _kernels_bwd(rule, chunk, interpret, res, do):
    return _call(rule, True, (*res, do), chunk, interpret)


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


# --------------------------------------------------------------------------- the call
def select_backend(platform: Optional[str] = None) -> str:
    """"pallas" on a TPU, "xla" elsewhere."""
    return "pallas" if (platform or jax.default_backend()) == "tpu" else "xla"


def _running_sum(g, chunk: int):
    """The sum of g (B, H, S, ...) from its chunk's first position on, f32. Its transpose, which jax makes, is the
    chunk's sum from each position to its end."""
    b, h, s = g.shape[:3]
    return jnp.cumsum(g.reshape(b, h, s // chunk, chunk, *g.shape[3:]), axis=3).reshape(g.shape)


def chunked_scan(rule: Rule, q, k, v, g, beta=None, mesh=None, *, chunk: int = CHUNK,
                 backend: Optional[str] = None, interpret: bool = False):
    """o (B, H, S, d_v), in v's type, of `rule`'s recurrence: what its entry point documents. q and k may hold a
    divisor of v's heads (a group's q and k: the top of the file), and `beta` may be None."""
    if v.shape[1] % k.shape[1] or q.shape[:3] != k.shape[:3]:
        raise ValueError(f"{rule.name}: q {q.shape} and k {k.shape} are no group's of v's {v.shape[1]} heads")
    if chunk & (chunk - 1) or chunk < 8:
        raise ValueError(f"{rule.name}: chunk {chunk} is no power of two of at least 8")
    if backend is None:
        backend = select_backend(mesh.devices.flat[0].platform if mesh is not None else None)
    seq = q.shape[2]
    pad = -seq % chunk
    if pad:  # beta 0 (v 0 where there is none), g 0: no write, no decay
        along_seq = lambda x: None if x is None else jnp.pad(  # noqa: E731
            x, ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 3))
        q, k, v, g, beta = (along_seq(x) for x in (q, k, v, g, beta))
    gam, beta = _running_sum(g.astype(F32), chunk), None if beta is None else beta.astype(F32)
    if backend == "xla":
        o = _xla_form(rule, q, k, v, gam, beta, chunk)
    elif backend == "pallas":
        def kernels(*operands):
            flat = [x.reshape(x.shape[0] * x.shape[1], *x.shape[2:]) for x in operands] + [None] * (5 - len(operands))
            o = _kernels(rule, *flat, chunk, interpret)
            return o.reshape(*operands[2].shape[:2], *o.shape[1:])

        operands = (q, k, v, gam) + (() if beta is None else (beta,))
        if mesh is not None and mesh.size > 1:
            from ray_tpu.parallel import ShardingRules

            spec = lambda x: ShardingRules().mesh_axes(  # noqa: E731
                ("batch", "heads") + (None,) * (x.ndim - 2), mesh=mesh, shape=x.shape)
            kernels = jax.shard_map(kernels, mesh=mesh, in_specs=tuple(spec(x) for x in operands),
                                    out_specs=spec(v), check_vma=False)
        o = kernels(*operands)
    else:
        raise ValueError(f"{rule.name}: backend {backend!r} is neither 'pallas' nor 'xla'")
    return o[:, :, :seq] if pad else o
