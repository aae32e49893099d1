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
two are made apart). `T` is made by doubling (`chunked_scan._unit_lower_inverses`),
the same in the XLA form and in the kernel.

`_chunk_gates`, `_chunk_fwd` and `_chunk_bwd` are that mathematics for one chunk
of one head on plain two-dimensional arrays, gates as rows `(1, C)`. They are
all of the rule that is its own: `ops/chunked_scan.py` walks them over a row,
in the XLA form (what runs off the TPU, differentiated by jax, and what the
kernels are held to) and in the Mosaic kernels `gdn_fwd` and `gdn_bwd`, G
heads a program (`chunk_128/heads_3of30` in their scope), and
`gated_delta_rule` is that walk applied to `RULE`.

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
rule beside this one on the same walk. What the scalar
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

from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops.chunked_scan import (BF16, CHUNK, F32, NT, TN, Rule, _col, _iotas, _mm, _row, _unit_lower_inverses,
                                      chunked_scan)
# Not used here: the benchmark (`select_backend`), the tests and `tools/gdn_bench.py` read these from this module.
from ray_tpu.ops.chunked_scan import NN, _bf16_parts, heads_per_program, select_backend  # noqa: F401


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


RULE = Rule(name="gated_delta_rule", kernels="gdn", gate_a_channel=False,
            functions=lambda: (_chunk_gates, _chunk_fwd, _chunk_bwd),
            chunk_flops=chunk_flops, transcendentals=lambda chunk, dk: chunk * chunk)


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
    return chunked_scan(RULE, q, k, v, g, beta, mesh, chunk=chunk, backend=backend, interpret=interpret)
