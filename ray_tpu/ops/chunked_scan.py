"""The walk of a chunked recurrent scan, once for every rule that has one (`ops/gated_delta_rule.py`, `ops/kda.py`):
a recurrence whose state is a matrix (d_k, d_v) a head, rewritten over chunks of C positions as matrix products, with
the state a chunk hands to the next in VMEM scratch.

A rule (`Rule`) brings the mathematics of one chunk of one head on plain two-dimensional arrays, three functions of
its own module: `chunk_gates(k, gam, beta)`, all that stands before the inverse of `I + A` (a dict with `a`, the
strictly lower triangular `A`, among whatever the other two read); `chunk_fwd(q, k, v, gam, beta, s, first=None)` ->
(o, the state after the chunk); `chunk_bwd(q, k, v, gam, beta, s, do, ds_new, first=None)` -> (dq, dk, dv, dgam,
dbeta, ds), where `first` is `chunk_gates`' dict with the inverse `t` added. `gam` is the running sum of the log decay
inside the chunk, `beta` a row (1, C). This module does the rest, the same for every rule:

The XLA form (`_xla_form`) maps `chunk_fwd` over batch and heads inside a `lax.scan` over the chunks: what runs off
the TPU, differentiated by jax, and what the kernels are held to. The Mosaic kernels `<rule>_fwd` and `<rule>_bwd`
call the same functions on their blocks, the chunks along a sequential grid axis with the state (`dS` in the
reverse walk, which starts from the row's end) in VMEM scratch. The forward kernel writes out the state every chunk
starts from, (B, H, S / C, d_k, d_v) f32, for the backward pass, which makes `T` and `N` again.

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
    (B, H, S) f32, S a whole number of chunks."""
    over_heads = jax.vmap(jax.vmap(rule.functions()[1]))

    def one_chunk(s, xs):
        qc, kc, vc, gc, bc = xs
        o, s = over_heads(qc, kc, vc, gc if rule.gate_a_channel else gc[:, :, None], bc[:, :, None], s)
        return s, o

    b, h, _, dk = k.shape
    s0 = jnp.zeros((b, h, dk, v.shape[-1]), F32)
    _, o = jax.lax.scan(one_chunk, s0, tuple(_chunked(x, chunk) for x in (q, k, v, gam, beta)))
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


def _heads_of_a_program(rule: Rule, k_ref, gam_ref, beta_ref, at):
    """[(k, gam, beta, `chunk_gates`' parts and the inverse `t`)] of chunk `at`, one a head of the program. The
    heads share nothing, so their doublings are independent chains: made together, level by level."""
    heads = [(k_ref[h], gam_ref[_gate_at(rule, h, at)], beta_ref[h, pl.ds(at, 1), :])
             for h in range(k_ref.shape[0])]
    firsts = [_once(rule.functions()[0])(*head) for head in heads]
    for first, t in zip(firsts, _once(_unit_lower_inverses)([first["a"] for first in firsts])):
        first["t"] = t
    return [(*head, first) for head, first in zip(heads, firsts)]


def _fwd_kernel(rule: Rule, q_ref, k_ref, v_ref, gam_ref, beta_ref, o_ref, states_ref, s_ref):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    for h, (k, gam, beta, first) in enumerate(_heads_of_a_program(rule, k_ref, gam_ref, beta_ref, i)):
        s = s_ref[h]
        states_ref[h, 0] = s
        o, s_new = _once(rule.functions()[1])(q_ref[h], k, v_ref[h], gam, beta, s, first)
        o_ref[h] = o.astype(o_ref.dtype)
        s_ref[h] = s_new


def _bwd_kernel(rule: Rule, q_ref, k_ref, v_ref, gam_ref, beta_ref, states_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dgam_ref, dbeta_ref, ds_ref):
    i = pl.program_id(1)
    at = pl.num_programs(1) - 1 - i  # the chunk: the walk is from the row's end

    @pl.when(i == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    for h, (k, gam, beta, first) in enumerate(_heads_of_a_program(rule, k_ref, gam_ref, beta_ref, at)):
        dq, dk, dv, dgam, dbeta, ds = _once(rule.functions()[2])(
            q_ref[h], k, v_ref[h], gam, beta, states_ref[h, 0], do_ref[h], ds_ref[h], first)
        dq_ref[h] = dq.astype(dq_ref.dtype)
        dk_ref[h] = dk.astype(dk_ref.dtype)
        dv_ref[h] = dv.astype(dv_ref.dtype)
        dgam_ref[_gate_at(rule, h, at)] = dgam
        dbeta_ref[h, pl.ds(at, 1), :] = dbeta
        ds_ref[h] = ds


def _lanes(d: int) -> int:
    return -(-d // 128) * 128


# What a program's heads may hold of VMEM between them: three quarters of the 16 MiB Mosaic gives a kernel on the v5e.
VMEM_BUDGET = 12 << 20
# Heads a program at most. On the v5e a layer-row of the Olmo-Hybrid cell (30 heads x 4,096) takes, forward + backward,
# 10.67 ms at one head a program, 7.65 at two, 7.28 at three, 7.20 at five, 7.05 at six, and the two kernels compile in
# 0.8, 1.6, 3.5, 6.5, 7.9 s (`tools/gdn_bench.py --heads`, PR 52): past three the doubling is within 16 % of its six-pass
# floor and a further head buys 1 % for twice the compile.
MAX_HEADS = 3


def heads_per_program(heads: int, seq: int, chunk: int, dk: int, dv: int, itemsize: int) -> int:
    """G, the heads one program walks side by side: the largest divisor of the `heads` the call holds (batch x
    heads on this device), at most `MAX_HEADS`, whose blocks, scratch and working set in the backward kernel (the
    larger one) fit `VMEM_BUDGET`; 1 where nothing divides them. Counted for a gate a row a head; a rule whose gate
    is as wide as the keys asks at f32 items (`Rule.itemsize`)."""
    n = seq // chunk
    # q, k, dq, dk; v, do, dv; the chunk's state; the two gates and their gradients, a whole row of them a head
    blocks = (4 * chunk * _lanes(dk) + 3 * chunk * _lanes(dv)) * itemsize + dk * _lanes(dv) * 4 + 4 * n * chunk * 4
    working = (8 * chunk * _lanes(chunk) + 4 * chunk * (_lanes(dk) + _lanes(dv))) * 4  # f32 values live at once
    a_head = 2 * blocks + dk * _lanes(dv) * 4 + working  # every block has two buffers; dS in scratch
    fit = max(1, min(MAX_HEADS, VMEM_BUDGET // a_head))
    return max(g for g in range(1, fit + 1) if heads % g == 0)


def plan(rule: Rule, k, v, chunk: int):
    """(G, the two scopes that name the plan: `chunk_128`, `heads_3of30`) for flat heads k (BH, S, d_k), v."""
    bh, seq, dk = k.shape
    g = heads_per_program(bh, seq, chunk, dk, v.shape[-1], rule.itemsize or k.dtype.itemsize)
    return g, f"chunk_{chunk}", f"heads_{g}of{bh}"


def _call(rule: Rule, backward: bool, operands, chunk: int, interpret: bool):
    """One of the two `pallas_call`s on flat heads. Forward: `operands` (q, k, v, gam, beta), q, k (BH, S, d_k), v
    (BH, S, d_v), gam (BH, S[, d_k]) and beta (BH, S) f32 -> (o, the state every chunk starts from (BH, S / C, d_k,
    d_v) f32). The reverse walk: (q, k, v, gam, beta, states, do) -> the five gradients, each laid out like what it is
    the gradient of. The grid is (programs of G heads, chunks), the second axis sequential: chunk i forward, chunk
    n - 1 - i in the reverse walk."""
    q, k, v, gam, beta = operands[:5]
    bh, seq, dk = k.shape
    dv, n = v.shape[-1], seq // chunk
    g, chunk_scope, heads_scope = plan(rule, k, v, chunk)
    at = (lambda i: n - 1 - i) if backward else (lambda i: i)
    per_chunk = lambda d: pl.BlockSpec((g, chunk, d), lambda h, i: (h, at(i), 0))  # noqa: E731
    per_head = pl.BlockSpec((g, n, chunk), lambda h, i: (h, 0, 0))
    states = pl.BlockSpec((g, 1, dk, dv), lambda h, i: (h, at(i), 0, 0))
    by_chunk = lambda x: x.reshape(bh, n, chunk)  # noqa: E731  (a row of gates a head, a chunk a sublane row)
    if rule.gate_a_channel:
        gate, gate_width, gates = per_chunk(dk), dk, (gam, by_chunk(beta))
    else:
        gate, gate_width, gates = per_head, 1, (by_chunk(gam), by_chunk(beta))
    qkv_gates = [per_chunk(dk), per_chunk(dk), per_chunk(dv), gate, per_head]
    like = lambda x, dtype=None: jax.ShapeDtypeStruct(x.shape, dtype or x.dtype)  # noqa: E731
    passes = 2 if backward else 1  # over q, k, v, o and over the gates: read, and in the reverse walk written too
    with jax.named_scope(chunk_scope), jax.named_scope(heads_scope):
        out = pl.pallas_call(
            functools.partial(_bwd_kernel if backward else _fwd_kernel, rule),
            grid=(bh // g, n),
            in_specs=qkv_gates + ([states, per_chunk(dv)] if backward else []),
            out_specs=qkv_gates if backward else [per_chunk(dv), states],
            out_shape=([like(q), like(k), like(v), like(gates[0], F32), like(gates[1], F32)] if backward
                       else [like(v), jax.ShapeDtypeStruct((bh, n, dk, dv), F32)]),
            scratch_shapes=[pltpu.VMEM((g, dk, dv), F32)],
            interpret=interpret,
            name=rule.kernels + ("_bwd" if backward else "_fwd"),
            compiler_params=None if interpret else pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
            cost_estimate=pl.CostEstimate(
                flops=bh * n * rule.chunk_flops(chunk, dk, dv, k.dtype, backward),
                bytes_accessed=bh * (seq * passes * (2 * dk + 2 * dv) * q.dtype.itemsize + n * dk * dv * 4
                                     + passes * seq * (gate_width + 1) * 4),
                transcendentals=bh * n * rule.transcendentals(chunk, dk)),
        )(q, k, v, *gates, *operands[5:])
    return (*out[:3], out[3].reshape(gam.shape), out[4].reshape(beta.shape)) if backward else out


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


def chunked_scan(rule: Rule, q, k, v, g, beta, mesh=None, *, chunk: int = CHUNK,
                 backend: Optional[str] = None, interpret: bool = False):
    """o (B, H, S, d_v), in v's type, of `rule`'s recurrence: what its entry point documents."""
    if chunk & (chunk - 1) or chunk < 8:
        raise ValueError(f"{rule.name}: chunk {chunk} is no power of two of at least 8")
    if backend is None:
        backend = select_backend(mesh.devices.flat[0].platform if mesh is not None else None)
    seq = q.shape[2]
    pad = -seq % chunk
    if pad:  # beta 0, g 0: no write, no decay
        along_seq = lambda x: jnp.pad(x, ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 3))  # noqa: E731
        q, k, v, g, beta = (along_seq(x) for x in (q, k, v, g, beta))
    gam, beta = _running_sum(g.astype(F32), chunk), beta.astype(F32)
    if backend == "xla":
        o = _xla_form(rule, q, k, v, gam, beta, chunk)
    elif backend == "pallas":
        def kernels(*operands):
            b, h = operands[0].shape[:2]
            o = _kernels(rule, *(x.reshape(b * h, *x.shape[2:]) for x in operands), chunk, interpret)
            return o.reshape(b, h, *o.shape[1:])

        operands = (q, k, v, gam, beta)
        if mesh is not None and mesh.size > 1:
            from ray_tpu.parallel import ShardingRules

            spec = lambda x: ShardingRules().mesh_axes(  # noqa: E731
                ("batch", "heads") + (None,) * (x.ndim - 2), mesh=mesh, shape=x.shape)
            kernels = jax.shard_map(kernels, mesh=mesh, in_specs=tuple(spec(x) for x in operands),
                                    out_specs=spec(q), check_vma=False)
        o = kernels(*operands)
    else:
        raise ValueError(f"{rule.name}: backend {backend!r} is neither 'pallas' nor 'xla'")
    return o[:, :, :seq] if pad else o
