"""The channel-wise gated delta rule (Kimi Delta Attention, Kimi Linear, arXiv:2510.26692): the recurrence
of `ops/gated_delta_rule.py` with a decay that is a vector of `d_k` a head and position, one factor for
every row of the state:

    S'  = Diag(exp(g_t)) S_{t-1}           S (d_k, d_v), f32, zero at a row's start; g_t (d_k,), <= 0
    u_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t u_t^T
    o_t = S_t^T q_t

In chunks of C positions, `gamma` (C, d_k) the running sum of g inside the chunk and `S` the state the
chunk starts from:

    A  = strict_tril(beta_i sum_c k_ic k_jc exp(gamma_ic - gamma_jc))        T = (I + A)^-1
    N  = T beta (V - (K e^gamma) S)
    O  = (Q e^gamma) S + tril(sum_c q_ic k_jc exp(gamma_ic - gamma_jc)) N
    S <- Diag(e^gamma_C) S + (K e^(gamma_C - gamma))^T N

What the vector costs. The scalar rule's decay `exp(gamma_i - gamma_j)` multiplies `K K^T` and `Q K^T`
after the product: one product each, operands as they come. Here it sits inside the contraction over
`d_k`, and `(K e^gamma)(K e^-gamma)^T` over a whole chunk overflows (128 positions at g = -1 are
`e^128`). So the two pairwise terms are made by halving (`_pairs`): at level h (h = C/2, C/4, ..., 1)
every block of 2h positions gives the entries that join its second half (rows) to its first (columns),
with the second half's first position m as reference: `(X e^(gamma - gamma_m)) (K e^(gamma_m - gamma))^T`,
rows after m and columns before it, so both exponents are <= 0 and their sum is `gamma_i - gamma_j` whatever
`gamma_m` is. The levels' masks tile the strict lower triangle; the diagonal has no decay. That is
`log2 C` products of the full C x d_k x C shape (a mask picks a level's entries) where the scalar rule
has one, every one of two f32 operands (the decay is in both): 7 x 6 MXU passes for one, and as many
again for each of the two gradients of a term. Everything else is the scalar rule's with `e^gamma` a
matrix: `T` by the same doubling (`_unit_lower_inverses`), the products against the state at full f32
precision (`_mm`), the state every chunk starts from written out for the backward pass, G heads a
program side by side (`heads_per_program`). No exponent above 0 is evaluated.

`_chunk_gates`, `_chunk_fwd` and `_chunk_bwd` are that mathematics for one chunk of one head on plain
two-dimensional arrays, and all of the rule that is its own: `ops/chunked_scan.py` walks them over a row, in
the XLA form (what runs off the TPU, differentiated by jax, and what the kernels are held to) and in the
Mosaic kernels `kda_fwd` and `kda_bwd`, and `kimi_delta_rule` is that walk applied to `RULE`. A head's blocks
and working set are the scalar rule's with a gate as wide as the keys: `heads_per_program` is asked at f32 items.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops.chunked_scan import (BF16, CHUNK, F32, NT, TN, Rule, _col, _iotas, _mm, _row, _unit_lower_inverses,
                                      chunked_scan)
from ray_tpu.ops.chunked_scan import select_backend  # noqa: F401  (the benchmark reads it from this module)


# --------------------------------------------------------------------------- the pairwise terms
def _levels(gam):
    """[(mask (C, C), e (C, d_k))] for h = 1, 2, ..., C / 2. `mask` picks the entries (i, j) with i in the
    second half and j in the first half of one block of 2h positions; `e` is `exp(gamma_i - gamma_m)` for the
    rows of a second half and `exp(gamma_m - gamma_i)` for those of a first, m the second half's first position:
    both <= 1. `gamma_m` reaches a block's rows by a product with a 0/1 matrix (bf16, exact) at three passes."""
    n = gam.shape[0]
    r, c = _iotas(n)
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    out, h = [], 1
    while h < n:
        level = h.bit_length() - 1
        mask = (((r ^ c) >> level) == 1) & (r > c)
        reference = (c == (r | (2 * h - 1)) - (h - 1)).astype(BF16)  # (i, m(i))
        gam_m = _mm(reference, gam)
        diff = jnp.where((rows & h) != 0, gam - gam_m, gam_m - gam)
        # <= 0 but for the rounding of `gam_m`; what is cut off carries no gradient away (jax differentiates the XLA form)
        out.append((mask, jnp.exp(diff - jax.lax.stop_gradient(jnp.maximum(diff, 0.0)))))
        h *= 2
    return out


def _pairs(x, y, levels, diagonal: bool):
    """`sum_c x_ic y_jc exp(gamma_ic - gamma_jc)` for j < i (and, with `diagonal`, j = i, where the decay is
    1), zeros above: (C, C) f32 of x, y (C, d_k) f32."""
    n = x.shape[0]
    out = jnp.zeros((n, n), F32)
    for mask, e in levels:
        out = jnp.where(mask, _mm(x * e, y * e, NT), out)
    if diagonal:
        r, c = _iotas(n)
        out = jnp.where(r == c, jnp.sum(x * y, axis=1, keepdims=True), out)
    return out


def _pairs_bwd(dm, x, y, levels, diagonal: bool):
    """(dx, dy) of `_pairs` for the cotangent `dm` (C, C), read on and under the diagonal alone. The decay's
    own gradient is `x dx - y dy` for gamma (the reference position's cancels), which the caller adds."""
    dx, dy = jnp.zeros_like(x), jnp.zeros_like(y)
    for mask, e in levels:
        picked = jnp.where(mask, dm, 0.0)
        dx += e * _mm(picked, y * e)
        dy += e * _mm(picked, x * e, TN)
    if diagonal:
        r, c = _iotas(x.shape[0])
        on = jnp.sum(jnp.where(r == c, dm, 0.0), axis=1, keepdims=True)
        dx += on * y
        dy += on * x
    return dx, dy


# --------------------------------------------------------------------------- one chunk
def _chunk_gates(k, gam, beta):
    """What a chunk's gates make, and `A` of them and the keys: all that stands before the inverse."""
    n = k.shape[0]
    r, c = _iotas(n)
    kf, beta_c = k.astype(F32), _col(beta)
    levels = _levels(gam)
    kk = _pairs(kf, kf, levels, diagonal=False)
    gam_end = gam[n - 1:n, :]  # (1, d_k)
    return dict(r=r, c=c, beta_c=beta_c, levels=levels, kk=kk, a=beta_c * kk, eg=jnp.exp(gam),
                to_end=jnp.exp(gam_end - gam), e_end=_col(jnp.exp(gam_end)))


def _chunk_parts(q, k, v, gam, beta, s, first=None):
    """What the forward and the backward pass of a chunk both need. `first`: `_chunk_gates`' parts with the
    inverse `t` among them, where the caller has made them (a kernel, for its heads together)."""
    if first is None:
        first = _chunk_gates(k, gam, beta)
        first["t"] = _unit_lower_inverses([first["a"]])[0]
    qf, kf = q.astype(F32), k.astype(F32)
    p = _pairs(qf, kf, first["levels"], diagonal=True)
    kg, qg = kf * first["eg"], qf * first["eg"]
    z = v.astype(F32) - _mm(kg, s)
    new = _mm(first["t"], first["beta_c"] * z)  # N
    return dict(first, qf=qf, kf=kf, p=p, kg=kg, qg=qg, z=z, new=new)


def _chunk_fwd(q, k, v, gam, beta, s, first=None):
    """One chunk of one head: q, k (C, d_k), v (C, d_v), `gam` (C, d_k) f32 the running sum of g inside the
    chunk, `beta` a row (1, C) f32, `s` (d_k, d_v) f32 the state before it. Returns (o (C, d_v) f32, the
    state after it)."""
    m = _chunk_parts(q, k, v, gam, beta, s, first)
    o = _mm(m["qg"], s) + _mm(m["p"], m["new"])
    s_new = m["e_end"] * s + _mm(m["kf"] * m["to_end"], m["new"], TN)
    return o, s_new


def _chunk_bwd(q, k, v, gam, beta, s, do, ds_new, first=None):
    """The chunk's vector-Jacobian product: from `do` (C, d_v) and the cotangent `ds_new` of the state after
    the chunk to (dq, dk, dv, dgam (C, d_k), dbeta (1, C), ds), all f32. The lines follow `_chunk_fwd`'s
    backwards."""
    m = _chunk_parts(q, k, v, gam, beta, s, first)
    r, c, levels, t, p, kk = m["r"], m["c"], m["levels"], m["t"], m["p"], m["kk"]
    eg, beta_c, new, to_end, qf, kf, kg, qg = (m[x] for x in ("eg", "beta_c", "new", "to_end", "qf", "kf", "kg", "qg"))
    do = do.astype(F32)
    rows = lambda x: jnp.sum(x, axis=1, keepdims=True)  # noqa: E731
    cols = lambda x: jnp.sum(x, axis=0, keepdims=True)  # noqa: E731
    # s_new = e_end s + (k to_end)^T new
    ds = m["e_end"] * ds_new
    kd = kf * to_end
    d_new = _mm(kd, ds_new)
    d_kd = _mm(new, ds_new, NT)
    dk = to_end * d_kd
    through_kd = d_kd * kd
    dgam = -through_kd
    dgam_end = _row(m["e_end"] * rows(ds_new * s)) + cols(through_kd)  # (1, d_k)
    # o = qg s + p new
    d_qg = _mm(do, s, NT)
    dq = eg * d_qg
    dgam += d_qg * qg
    ds += _mm(qg, do, TN)
    dp = _mm(do, new, NT)
    d_new += _mm(p, do, TN)
    # p = pairs(q, k), on and under the diagonal
    dx, dy = _pairs_bwd(dp, qf, kf, levels, diagonal=True)
    dq += dx
    dk += dy
    dgam += qf * dx - kf * dy
    # new = (I + a)^-1 (beta z)
    dr = _mm(t, d_new, TN)
    da = jnp.where(r > c, -_mm(dr, new, NT), 0.0)
    # a = beta_i pairs(k, k), under the diagonal
    dbeta_c = rows(da * kk)
    dx, dy = _pairs_bwd(beta_c * da, kf, kf, levels, diagonal=False)
    dk += dx + dy
    dgam += kf * (dx - dy)
    # z = v - kg s
    dv = beta_c * dr
    dbeta_c += rows(dr * m["z"])
    d_kg = -_mm(dv, s, NT)
    ds += _mm(kg, -dv, TN)
    dk += eg * d_kg
    dgam += d_kg * kg
    last = jax.lax.broadcasted_iota(jnp.int32, (gam.shape[0], 1), 0) == gam.shape[0] - 1
    dgam += jnp.where(last, dgam_end, 0.0)
    return dq, dk, dv, dgam, _row(dbeta_c), ds


def mxu_passes(chunk: int, dk: int, dv: int, backward: bool = False) -> float:
    """MXU passes of 128^3 multiply-adds the kernels issue for one chunk of one head: six for a product of two
    f32 arrays, three where one operand is a bf16 0/1 matrix."""
    unit = 128 ** 3
    ccd, cdd, ccv = chunk * chunk * dk / unit, chunk * dk * dv / unit, chunk * chunk * dv / unit
    levels = chunk.bit_length() - 1
    doubling = 6 * 2 * (chunk.bit_length() - 2) * chunk ** 3 / unit
    passes = doubling + levels * (3 + 2 * 6) * ccd + 6 * (cdd + ccv)  # gamma_m, K K^T, Q K^T; K S; T R
    if not backward:
        return passes + 6 * (2 * cdd + ccv)  # Q S, K^T N; P N
    # the two terms' two gradients a level; k dS', N dS'^T, dO S^T, qg^T dO, dV S^T, kg^T dV; dO N^T, P^T dO, T^T dN, dR N^T
    return passes + levels * 4 * 6 * ccd + 6 * (6 * cdd + 4 * ccv)


def chunk_flops(chunk: int, dk: int, dv: int, backward: bool = False) -> int:
    """Multiply-adds (2 FLOP each) the kernels issue for one chunk of one head, a product counted once for
    every MXU pass it takes (`mxu_passes`): what XLA is told."""
    return int(2 * 128 ** 3 * mxu_passes(chunk, dk, dv, backward))


RULE = Rule(name="kimi_delta_rule", kernels="kda", gate_a_channel=True, itemsize=4,
            functions=lambda: (_chunk_gates, _chunk_fwd, _chunk_bwd),
            chunk_flops=lambda chunk, dk, dv, dtype, backward: chunk_flops(chunk, dk, dv, backward),
            transcendentals=lambda chunk, dk: chunk * dk * (chunk.bit_length() + 1))


def kimi_delta_rule(q, k, v, g, beta, mesh=None, *, chunk: int = CHUNK,
                    backend: Optional[str] = None, interpret: bool = False):
    """o (B, H, S, d_v), in v's type, of the recurrence at the top of the file.

    q, k: (B, H, S, d_k), as the layer hands them (L2-normalised, q scaled); v: (B, H, S, d_v); g (B, H, S,
    d_k) the log decay of every channel (<= 0) and beta (B, H, S), f32. Every row starts from a zero state. S
    need not be a whole number of chunks. `backend` and `mesh` as `gated_delta_rule`'s: the kernels on a TPU
    ("pallas"), inside a shard_map on more than one device (batch over (data, fsdp), heads over tensor), the
    XLA form elsewhere."""
    return chunked_scan(RULE, q, k, v, g, beta, mesh, chunk=chunk, backend=backend, interpret=interpret)
