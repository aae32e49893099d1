"""The gated delta rule (Gated DeltaNet, Yang et al. 2024), the recurrence of
a linear-attention layer whose state is a matrix a head:

    S'  = exp(g_t) S_{t-1}                 S (d_k, d_v), f32, zero at a row's start
    u_t = beta_t (v_t - S'^T k_t)          what the state does not yet answer for k_t
    S_t = S' + k_t u_t^T
    o_t = S_t^T q_t

with `g_t <= 0` the log of a decay and `beta_t` in (0, 2) the strength of the
write (above 1 the state's transition `exp(g)(I - beta k k^T)` has a negative
eigenvalue). Token by token it is 4,096 dependent rank-one updates a row; in
chunks of C positions it is matrix products (the WY form). With `gamma` the
running sum of `g` inside the chunk, `D_ij = exp(gamma_i - gamma_j)` for
`j <= i`, and `S` the state the chunk starts from:

    A  = strict_tril(beta_i (k_i . k_j) D_ij)        T = (I + A)^-1
    R  = beta (V - exp(gamma) (K S))                  N = T R      (the chunk's u)
    O  = (Q exp(gamma)) S + tril((Q K^T) D) N
    S <- exp(gamma_C) S + (K exp(gamma_C - gamma))^T N

(`N = U - W S` with `W = T (beta exp(gamma) K)`, `U = T (beta V)` where the
two are made apart). `T` is made by doubling: the inverse of the block
diagonal of `I + A` at block size 2b is `X - X E X`, `X` that at size b and
`E` the entries of `A` that join a block's halves: two C x C products a level
above the first
and no row-by-row substitution, the same in the XLA form and in the kernel.

`_chunk_fwd` and `_chunk_bwd` are that mathematics for one chunk of one head on
plain two-dimensional arrays, gates as rows `(1, C)`: the XLA form maps
`_chunk_fwd` over batch and heads inside a scan over the chunks (what runs off
the TPU, differentiated by jax, and what the kernels are held to); the Mosaic
kernels `gdn_fwd` and `gdn_bwd` call the same two functions on their blocks,
the chunks along a sequential grid axis with the state (`dS` in the reverse
walk) in VMEM scratch. The forward kernel writes out the state every chunk
starts from, (B, H, S / C, d_k, d_v) f32, for the backward pass, which makes
`T` and `N` again.

A program walks G heads, unrolled in one body (`heads_per_program`: a divisor
of the heads the call holds, by the VMEM they need; the kernels' scope says
which, `chunk_128/heads_3of30`). The doubling is twelve products of C^3 each
waiting for the one before it, and a head alone has nothing to issue while
one drains; heads share nothing, so G of them are G independent chains, made
level by level side by side (`_unit_lower_inverses`): Mosaic overlaps products
that stand next to each other in the program, and does not lift a later head's
over an earlier head's chain (heads unrolled one after the other gained nothing).

The MXU passes of a product follow its operands' types as they arrive (`_mm`).
Every product of two f32 arrays (the state, the decay, `T`, `N`, a cotangent
on both sides) is at full f32 precision, six passes. A product of a bf16 array
(q, k, do in a bf16 model) with an f32 one is three passes, the bf16 array
against the f32 one's three bf16 parts: the six-pass product of its cast to
f32 without the three passes that multiply the cast's zero parts; so that the
bf16 array itself is the operand, a row scaling stands on the product's other
side (`(q eg) S = eg (q S)`, `(k to_end)^T N = k^T (to_end N)`). `K K^T` and
`Q K^T` multiply the operands as they come (bf16 in a bf16 model, f32
accumulation). With f32 operands every product is six passes.

The rule with a decay a channel (`ops/kda.py`, Kimi Delta Attention) is a
file beside this one that imports this one's helpers (`_mm`,
`_unit_lower_inverses`, `heads_per_program`, `_col`, `_row`). What the scalar
decay lets this file do is take it out of every contraction over `d_k`:
`exp(gamma_i - gamma_j)` multiplies `K K^T` and `Q K^T` after the product, and
`exp(gamma)` is a row's scale that can stand on a product's other side, so q, k
and do reach the MXU as they come and a pairwise term is one product of C x d_k
x C. A vector decay sits inside the contraction: each of the two pairwise terms
becomes `log2 C` masked products of that shape, both operands scaled by an
exponential and therefore f32 (7 x 6 passes at C = 128 for one, and as many for
each of its two gradients), and no product against the state has a bf16
operand left. So the two rules share the doubling, the plan and the walk, and
not `_chunk_fwd` / `_chunk_bwd`: a family of two, not one operator with an
option (ROADMAP B7).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Positions a chunk; a row is padded to a whole number of them (beta 0, g 0: no write, no decay). On the v5e a
# layer-row of the Olmo-Hybrid cell takes 12.0 ms forward and backward at 128, 13.7 at 64, 18.2 at 32
# (`tools/gdn_bench.py`, PR 51), and the states kept for the backward pass halve with each doubling.
CHUNK = 128
F32, BF16 = jnp.float32, jnp.bfloat16
NT = (((1,), (1,)), ((), ()))  # a @ b.T
NN = (((1,), (0,)), ((), ()))  # a @ b
TN = (((0,), (0,)), ((), ()))  # a.T @ b


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
    the block size of the block diagonal's inverse; a level's two products for every matrix before the
    next level's, so that each product stands beside the other matrices' and not behind its own last one."""
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


def _unit_lower_inverse(a):
    return _unit_lower_inverses([a])[0]


def _chunk_gates(k, gam, beta):
    """What a chunk's gates make, and `A` of them and the keys: all that stands before the inverse."""
    n = k.shape[0]
    r, c = _iotas(n)
    gam_c, beta_c = _col(gam), _col(beta)
    decay = jnp.exp(jnp.where(r >= c, gam_c - gam, -jnp.inf))  # D: 0 above the diagonal, 1 on it
    a = jnp.where(r > c, beta_c * _mm(k, k, NT) * decay, 0.0)
    last = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1) == n - 1
    gam_end = jnp.sum(jnp.where(last, gam, 0.0), axis=1, keepdims=True)  # (1, 1)
    return dict(r=r, c=c, gam_c=gam_c, beta_c=beta_c, decay=decay, a=a, eg=jnp.exp(gam_c), last=last,
                gam_end=gam_end, to_end=jnp.exp(gam_end - gam_c))


def _chunk_parts(q, k, v, gam, beta, s, first=None):
    """What the forward and the backward pass of a chunk both need. `first`: `_chunk_gates`' parts with the
    inverse `t` among them, where the caller has made them (a kernel, for its heads together)."""
    if first is None:
        first = _chunk_gates(k, gam, beta)
        first["t"] = _unit_lower_inverse(first["a"])
    p = _mm(q, k, NT) * first["decay"]
    ks = _mm(k, s)
    z = v.astype(F32) - first["eg"] * ks
    new = _mm(first["t"], first["beta_c"] * z)  # N
    return dict(first, p=p, ks=ks, z=z, new=new)


def _chunk_fwd(q, k, v, gam, beta, s, first=None):
    """One chunk of one head: q, k (C, d_k), v (C, d_v), `gam` the running sum
    of g inside the chunk and `beta` as rows (1, C) f32, `s` (d_k, d_v) f32 the
    state before it. Returns (o (C, d_v) f32, the state after it)."""
    m = _chunk_parts(q, k, v, gam, beta, s, first)
    o = m["eg"] * _mm(q, s) + _mm(m["p"], m["new"])
    s_new = jnp.exp(m["gam_end"]) * s + _mm(k, m["to_end"] * m["new"], TN)
    return o, s_new


def _chunk_bwd(q, k, v, gam, beta, s, do, ds_new, first=None):
    """The chunk's vector-Jacobian product: from `do` (C, d_v) and the
    cotangent `ds_new` of the state after the chunk to (dq, dk, dv, dgam (1, C),
    dbeta (1, C), ds), all f32. The lines follow `_chunk_fwd`'s backwards."""
    m = _chunk_parts(q, k, v, gam, beta, s, first)
    r, c, decay, a, t, p = m["r"], m["c"], m["decay"], m["a"], m["t"], m["p"]
    eg, beta_c, ks, new, to_end = m["eg"], m["beta_c"], m["ks"], m["new"], m["to_end"]
    qf, kf = q.astype(F32), k.astype(F32)  # for the sums over a row; the products take q, k, do as they come
    rows = lambda x: jnp.sum(x, axis=1, keepdims=True)  # noqa: E731
    cols = lambda x: jnp.sum(x, axis=0, keepdims=True)  # noqa: E731
    # s_new = exp(gam_end) s + (k to_end)^T new
    e_end, kd = jnp.exp(m["gam_end"]), kf * to_end
    ds = e_end * ds_new
    d_new = to_end * _mm(k, ds_new)
    d_kd = _mm(new, ds_new, NT)
    dk = to_end * d_kd
    through_kd = rows(d_kd * kd)
    dgam_c = -through_kd
    dgam_end = e_end * jnp.sum(rows(ds_new * s), axis=0, keepdims=True) + cols(through_kd)
    # o = (q eg) s + p new
    qg = qf * eg
    d_qg = _mm(do, s, NT)
    dq = eg * d_qg
    dgam_c += rows(d_qg * qg)
    ds += _mm(qg, do, TN)
    dp = _mm(do, new, NT)
    d_new += _mm(p, do, TN)
    # p = (q k^T) decay, on and under the diagonal
    dp_decayed = dp * decay
    dq += _mm(dp_decayed, k)
    dk += _mm(dp_decayed, q, TN)
    through_p = dp * p
    dgam_c += rows(through_p)
    dgam_r = -cols(through_p)
    # new = (I + a)^-1 (beta z)
    dr = _mm(t, d_new, TN)
    da = -_mm(dr, new, NT)
    # a = beta_i (k_i . k_j) decay, under the diagonal
    da_decayed = jnp.where(r > c, da * decay, 0.0)
    d_kb = _mm(da_decayed, k)
    dk += _mm(beta_c * da_decayed, k, TN) + beta_c * d_kb
    dbeta_c = rows(d_kb * kf)
    through_a = da * a
    dgam_c += rows(through_a)
    dgam_r -= cols(through_a)
    # z = v - eg (k s)
    dv = beta_c * dr
    dbeta_c += rows(dr * m["z"])
    d_ks = -(beta_c * eg) * dr
    dgam_c += rows(d_ks * ks)
    dk += _mm(d_ks, s, NT)
    ds += _mm(k, d_ks, TN)
    dgam = _row(dgam_c) + dgam_r + jnp.where(m["last"], dgam_end, 0.0)
    return dq, dk, dv, dgam, _row(dbeta_c), ds


# --------------------------------------------------------------------------- the XLA form
def _chunked(x, chunk: int):
    """(B, H, S, ...) -> (S / C, B, H, C, ...): the chunks first, for a scan."""
    b, h, s = x.shape[:3]
    x = x.reshape(b, h, s // chunk, chunk, *x.shape[3:])
    return jnp.moveaxis(x, 2, 0)


def _xla_gated_delta_rule(q, k, v, gam, beta, chunk: int):
    """The chunked form on whole arrays: q, k (B, H, S, d_k), v (B, H, S, d_v),
    `gam` and `beta` (B, H, S) f32, S a whole number of chunks."""
    over_heads = jax.vmap(jax.vmap(_chunk_fwd))

    def one_chunk(s, xs):
        qc, kc, vc, gc, bc = xs
        o, s = over_heads(qc, kc, vc, gc[:, :, None], bc[:, :, None], s)
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


def _heads_of_a_program(k_ref, gam_ref, beta_ref, at):
    """[(k, gam, beta, `_chunk_gates`' parts and the inverse `t`)] of chunk `at`, one a head of the program. The
    heads share nothing, so their doublings are independent chains: made together, level by level."""
    heads = [(k_ref[h], gam_ref[h, pl.ds(at, 1), :], beta_ref[h, pl.ds(at, 1), :]) for h in range(k_ref.shape[0])]
    firsts = [_once(_chunk_gates)(*head) for head in heads]
    for first, t in zip(firsts, _once(_unit_lower_inverses)([first["a"] for first in firsts])):
        first["t"] = t
    return [(*head, first) for head, first in zip(heads, firsts)]


def _fwd_kernel(q_ref, k_ref, v_ref, gam_ref, beta_ref, o_ref, states_ref, s_ref):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    heads = _heads_of_a_program(k_ref, gam_ref, beta_ref, i)
    for h, (k, gam, beta, first) in enumerate(heads):
        s = s_ref[h]
        states_ref[h, 0] = s
        o, s_new = _once(_chunk_fwd)(q_ref[h], k, v_ref[h], gam, beta, s, first)
        o_ref[h] = o.astype(o_ref.dtype)
        s_ref[h] = s_new


def _bwd_kernel(q_ref, k_ref, v_ref, gam_ref, beta_ref, states_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dgam_ref, dbeta_ref, ds_ref):
    i = pl.program_id(1)
    at = pl.num_programs(1) - 1 - i  # the chunk: the walk is from the row's end

    @pl.when(i == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    heads = _heads_of_a_program(k_ref, gam_ref, beta_ref, at)
    for h, (k, gam, beta, first) in enumerate(heads):
        dq, dk, dv, dgam, dbeta, ds = _once(_chunk_bwd)(
            q_ref[h], k, v_ref[h], gam, beta, states_ref[h, 0], do_ref[h], ds_ref[h], first)
        dq_ref[h] = dq.astype(dq_ref.dtype)
        dk_ref[h] = dk.astype(dk_ref.dtype)
        dv_ref[h] = dv.astype(dv_ref.dtype)
        dgam_ref[h, pl.ds(at, 1), :] = dgam
        dbeta_ref[h, pl.ds(at, 1), :] = dbeta
        ds_ref[h] = ds


def mxu_passes(chunk: int, dk: int, dv: int, dtype, backward: bool = False) -> float:
    """MXU passes of 128^3 multiply-adds the kernels issue for one chunk of one head with q, k, v (and do)
    of `dtype`: six for a product of two f32 arrays, three where one operand is bf16, one where both are."""
    bf16 = jnp.dtype(dtype) == BF16
    operands, one_cast = (1, 3) if bf16 else (6, 6)
    unit = 128 ** 3
    ccd, cdd, ccv = chunk * chunk * dk / unit, chunk * dk * dv / unit, chunk * chunk * dv / unit
    doubling = 6 * 2 * (chunk.bit_length() - 2) * chunk ** 3 / unit
    passes = doubling + 2 * operands * ccd + one_cast * cdd + 6 * ccv  # K K^T, Q K^T; K S; T R
    if not backward:
        return passes + 2 * one_cast * cdd + 6 * ccv  # Q S, K^T N; P N
    # k dS', dO S^T, qg^T dO, K^T dKS; dO N^T, P^T dO; dP K, dP^T Q, dA K, (beta dA)^T K
    passes += one_cast * (4 * cdd + 2 * ccv + 4 * ccd)
    return passes + 6 * (2 * cdd + 2 * ccv)  # N dS'^T, dKS S^T; T^T dN, dR N^T


def chunk_flops(chunk: int, dk: int, dv: int, dtype, backward: bool = False) -> int:
    """Multiply-adds (2 FLOP each) the kernels issue for one chunk of one head, the doubling's
    `2 (log2 C - 1)` products of C^3 included and a product counted once for every MXU pass it takes
    (`mxu_passes`): what XLA is told."""
    return int(2 * 128 ** 3 * mxu_passes(chunk, dk, dv, dtype, backward))


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
    larger one) fit `VMEM_BUDGET`; 1 where nothing divides them."""
    n = seq // chunk
    # q, k, dq, dk; v, do, dv; the chunk's state; the two gates and their gradients, a whole row of them a head
    blocks = (4 * chunk * _lanes(dk) + 3 * chunk * _lanes(dv)) * itemsize + dk * _lanes(dv) * 4 + 4 * n * chunk * 4
    working = (8 * chunk * _lanes(chunk) + 4 * chunk * (_lanes(dk) + _lanes(dv))) * 4  # f32 values live at once
    a_head = 2 * blocks + dk * _lanes(dv) * 4 + working  # every block has two buffers; dS in scratch
    fit = max(1, min(MAX_HEADS, VMEM_BUDGET // a_head))
    return max(g for g in range(1, fit + 1) if heads % g == 0)


def _compiler_params(interpret):
    return None if interpret else pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def _gates_by_chunk(x, chunk: int):
    bh, s = x.shape
    return x.reshape(bh, s // chunk, chunk)


def _plan(k, v, chunk):
    """(G, the two scopes that name the plan: `chunk_128`, `heads_3of30`)."""
    bh, seq, dk = k.shape
    g = heads_per_program(bh, seq, chunk, dk, v.shape[-1], k.dtype.itemsize)
    return g, f"chunk_{chunk}", f"heads_{g}of{bh}"


def _fwd(q, k, v, gam, beta, chunk, interpret):
    """Flat heads: q, k (BH, S, d_k), v (BH, S, d_v), gam, beta (BH, S) f32."""
    bh, seq, dk = k.shape
    dv, n = v.shape[-1], seq // chunk
    g, chunk_scope, heads_scope = _plan(k, v, chunk)
    per_chunk = lambda d: pl.BlockSpec((g, chunk, d), lambda h, i: (h, i, 0))  # noqa: E731
    per_head = pl.BlockSpec((g, n, chunk), lambda h, i: (h, 0, 0))
    with jax.named_scope(chunk_scope), jax.named_scope(heads_scope):
        return pl.pallas_call(
            _fwd_kernel,
            grid=(bh // g, n),
            in_specs=[per_chunk(dk), per_chunk(dk), per_chunk(dv), per_head, per_head],
            out_specs=[per_chunk(dv), pl.BlockSpec((g, 1, dk, dv), lambda h, i: (h, i, 0, 0))],
            out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                       jax.ShapeDtypeStruct((bh, n, dk, dv), F32)],
            scratch_shapes=[pltpu.VMEM((g, dk, dv), F32)],
            interpret=interpret,
            name="gdn_fwd",
            compiler_params=_compiler_params(interpret),
            cost_estimate=pl.CostEstimate(
                flops=bh * n * chunk_flops(chunk, dk, dv, k.dtype),
                bytes_accessed=bh * (seq * (2 * dk + 2 * dv) * q.dtype.itemsize + n * dk * dv * 4 + 2 * seq * 4),
                transcendentals=bh * n * chunk * chunk),
        )(q, k, v, _gates_by_chunk(gam, chunk), _gates_by_chunk(beta, chunk))


def _bwd(q, k, v, gam, beta, states, do, chunk, interpret):
    bh, seq, dk = k.shape
    dv, n = v.shape[-1], seq // chunk
    g, chunk_scope, heads_scope = _plan(k, v, chunk)
    per_chunk = lambda d: pl.BlockSpec((g, chunk, d), lambda h, i: (h, n - 1 - i, 0))  # noqa: E731
    per_head = pl.BlockSpec((g, n, chunk), lambda h, i: (h, 0, 0))
    gates = jax.ShapeDtypeStruct((bh, n, chunk), F32)
    with jax.named_scope(chunk_scope), jax.named_scope(heads_scope):
        dq, dk_, dv_, dgam, dbeta = pl.pallas_call(
            _bwd_kernel,
            grid=(bh // g, n),
            in_specs=[per_chunk(dk), per_chunk(dk), per_chunk(dv), per_head, per_head,
                      pl.BlockSpec((g, 1, dk, dv), lambda h, i: (h, n - 1 - i, 0, 0)), per_chunk(dv)],
            out_specs=[per_chunk(dk), per_chunk(dk), per_chunk(dv), per_head, per_head],
            out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct(k.shape, k.dtype),
                       jax.ShapeDtypeStruct(v.shape, v.dtype), gates, gates],
            scratch_shapes=[pltpu.VMEM((g, dk, dv), F32)],
            interpret=interpret,
            name="gdn_bwd",
            compiler_params=_compiler_params(interpret),
            cost_estimate=pl.CostEstimate(
                flops=bh * n * chunk_flops(chunk, dk, dv, k.dtype, backward=True),
                bytes_accessed=bh * (seq * (4 * dk + 4 * dv) * q.dtype.itemsize + n * dk * dv * 4 + 4 * seq * 4),
                transcendentals=bh * n * chunk * chunk),
        )(q, k, v, _gates_by_chunk(gam, chunk), _gates_by_chunk(beta, chunk), states, do)
    return dq, dk_, dv_, dgam.reshape(bh, seq), dbeta.reshape(bh, seq)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kernels(q, k, v, gam, beta, chunk, interpret):
    return _fwd(q, k, v, gam, beta, chunk, interpret)[0]


def _kernels_fwd(q, k, v, gam, beta, chunk, interpret):
    o, states = _fwd(q, k, v, gam, beta, chunk, interpret)
    return o, (q, k, v, gam, beta, states)


def _kernels_bwd(chunk, interpret, res, do):
    return _bwd(*res, do, chunk, interpret)


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


# --------------------------------------------------------------------------- the call
def select_backend(platform: Optional[str] = None) -> str:
    """"pallas" on a TPU, "xla" elsewhere."""
    return "pallas" if (platform or jax.default_backend()) == "tpu" else "xla"


def _running_sum(g, chunk: int):
    """The sum of g from its chunk's first position on, (..., S) f32. Its
    transpose, which jax makes, is the chunk's sum from each position to its end."""
    return jnp.cumsum(g.reshape(*g.shape[:-1], -1, chunk), axis=-1).reshape(g.shape)


def gated_delta_rule(q, k, v, g, beta, mesh=None, *, chunk: int = CHUNK,
                     backend: Optional[str] = None, interpret: bool = False):
    """o (B, H, S, d_v), in v's type, of the recurrence at the top of the file.

    q, k: (B, H, S, d_k), as the layer hands them (L2-normalised, q scaled);
    v: (B, H, S, d_v); g (log decay, <= 0) and beta: (B, H, S), f32. Every row
    starts from a zero state. S need not be a whole number of chunks.
    backend: "pallas" | "xla" | None (`select_backend` for the platform the
      computation is compiled for: the mesh's where there is one).
    mesh: the jax.sharding.Mesh the surrounding jit shards over: XLA cannot
      partition a Mosaic call, so on more than one device the kernels run
      inside a shard_map, batch over (data, fsdp) and heads over tensor, as
      `flash_attention(mesh=)`: heads and rows are independent."""
    if chunk & (chunk - 1) or chunk < 8:
        raise ValueError(f"gated_delta_rule: chunk {chunk} is no power of two of at least 8")
    if backend is None:
        backend = select_backend(mesh.devices.flat[0].platform if mesh is not None else None)
    seq = q.shape[2]
    pad = -seq % chunk
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) for x in (q, k, v))
        g, beta = (jnp.pad(x, ((0, 0), (0, 0), (0, pad))) for x in (g, beta))
    gam, beta = _running_sum(g.astype(F32), chunk), beta.astype(F32)
    if backend == "xla":
        o = _xla_gated_delta_rule(q, k, v, gam, beta, chunk)
    elif backend == "pallas":
        def kernels(q, k, v, gam, beta):
            b, h = q.shape[:2]
            flat = lambda x: x.reshape(b * h, *x.shape[2:])  # noqa: E731
            o = _kernels(flat(q), flat(k), flat(v), flat(gam), flat(beta), chunk, interpret)
            return o.reshape(b, h, *o.shape[1:])

        if mesh is not None and mesh.size > 1:
            from ray_tpu.parallel import ShardingRules

            rules = ShardingRules()
            wide = rules.mesh_axes(("batch", "heads", None, None), mesh=mesh, shape=q.shape)
            gates = rules.mesh_axes(("batch", "heads", None), mesh=mesh, shape=gam.shape)
            kernels = jax.shard_map(kernels, mesh=mesh, in_specs=(wide, wide, wide, gates, gates),
                                    out_specs=wide, check_vma=False)
        o = kernels(q, k, v, gam, beta)
    else:
        raise ValueError(f"gated_delta_rule: backend {backend!r} is neither 'pallas' nor 'xla'")
    return o[:, :, :seq] if pad else o
