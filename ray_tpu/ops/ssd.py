"""The state-space duality scan of a Mamba-2 mixer (Dao & Gu 2024, arXiv:2405.21060), the recurrence of a layer
whose state is a matrix a head, written by a plain decayed outer product:

    S_t = a_t S_{t-1} + B_t (dt_t x_t)^T        S (N, P), f32, zero at a row's start;  a_t = exp(dt_t A), A < 0
    o_t = S_t^T C_t + D x_t

with x (P a head), `dt_t > 0` and `A`, `D` a number a head, and `B_t`, `C_t` (N) **one array for the heads of a group**
(all 64 of granite-4.0-h's). It is the linear-attention recurrence with k = B, q = C, v = dt x and a scalar decay, and no
delta: nothing is read back from the state before the write, so a chunk of C positions has no triangular solve. With
`gamma` the running sum of `g = dt A` inside the chunk, `L_ij = exp(gamma_i - gamma_j)` for `j <= i` and `S` the state
the chunk starts from:

    O  = exp(gamma) (Q S) + ((Q K^T) L) V
    S <- exp(gamma_C) S + K^T (exp(gamma_C - gamma) V)

`_chunk_gates`, `_chunk_fwd` and `_chunk_bwd` are that mathematics for one chunk of one head on plain two-dimensional
arrays, and all of the rule that is its own: `ops/chunked_scan.py` walks them over a row, in the XLA form (off the TPU,
differentiated by jax) and in the Mosaic kernels `ssd_fwd` and `ssd_bwd`. What this rule leaves out of the walk is the
inverse (`Rule.inverse` False) and `beta`; what it asks of it is a group's q and k: B and C stay (B, groups, S, N) in
HBM, a program walks G heads of one group on the group's blocks (`chunk_128/heads_4of64/group_64` in the kernels'
scope), and dB and dC are summed over the group's heads inside the reverse walk.

Every exponential is of a difference that is <= 0 (`gamma` falls along a chunk): `exp(gamma_i - gamma_j)` for i >= j,
`exp(gamma)`, `exp(gamma_C - gamma)`; never `exp(gamma_i) exp(-gamma_j)`, whose second factor overflows f32 where a
chunk's sum passes -88 (`A` up to 16, `dt` up to 0.1: 128 positions reach -205). The state, the decay and every product
that touches them are f32; x (as v = dt x), B and C reach the MXU in the type they arrive in (`chunked_scan._mm`: three
passes against an f32 operand, one for `C B^T` and `dO V^T`).

`ssd` is the operator a mixer calls: `dt` (after its softplus) and `A_log` make the decay and scale x under the scope
`ssd_gates`, the walk runs under `ssd_scan`, and `D x` is added to what it returns.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops.chunked_scan import (BF16, F32, NT, TN, Rule, _call, _col, _iotas, _mm, _row, _running_sum,
                                      chunked_scan)
# Not used here: the benchmark and the tools read these from this module, as from the delta rules'.
from ray_tpu.ops.chunked_scan import heads_per_program, select_backend  # noqa: F401

# Positions a chunk. On the v5e a layer-row of the granite-4.0-h-micro cell (64 heads of 64 on one B and C of 128,
# 4,096 positions) takes forward + backward 2.19 ms at 128 (four heads a program) and 2.42-2.65 ms at 256 (eight heads
# to one: twice the masked product's work for half the states kept; `tools/ssd_bench.py`, PR 73). The published
# kernel's `mamba_chunk_size` 256 is its own tuning size and changes no result.
CHUNK = 128
# Heads a program at most. With no inverse there is no chain to hide: what a further head buys is fewer, larger grid
# steps: 2.85 ms a layer-row at one head, 2.35 at two, 2.19 at four, 2.10 at eight, which the walk's VMEM estimate does
# not admit (sixteen fail Mosaic's 16 MiB by 76 KB; `tools/ssd_bench.py --heads`, PR 73).
HEADS = 4


def _chunk_gates(k, gam, beta=None):
    """What a chunk's gate makes: the decay between its positions and to its two ends. No `a`: nothing to invert."""
    del beta  # the walk's third gate: this rule has none
    n = k.shape[0]
    r, c = _iotas(n)
    gam_c = _col(gam)
    decay = jnp.exp(jnp.where(r >= c, gam_c - gam, -jnp.inf))  # L: 0 above the diagonal, 1 on it
    last = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1) == n - 1
    gam_end = jnp.sum(jnp.where(last, gam, 0.0), axis=1, keepdims=True)  # (1, 1)
    return dict(decay=decay, eg=jnp.exp(gam_c), last=last, gam_end=gam_end, to_end=jnp.exp(gam_end - gam_c))


def _chunk_fwd(q, k, v, gam, beta, s, first=None):
    """One chunk of one head: q = C, k = B (C, N), v = dt x (C, P), `gam` the running sum of g inside the chunk as a
    row (1, C) f32, `s` (N, P) f32 the state before it. Returns (o (C, P) f32, the state after it)."""
    m = first or _chunk_gates(k, gam)
    p = _mm(q, k, NT) * m["decay"]
    o = m["eg"] * _mm(q, s) + _mm(p, v)
    s_new = jnp.exp(m["gam_end"]) * s + _mm(k, m["to_end"] * v, TN)
    return o, s_new


def _chunk_bwd(q, k, v, gam, beta, s, do, ds_new, first=None):
    """The chunk's vector-Jacobian product: from `do` (C, P) and the cotangent `ds_new` of the state after the chunk
    to (dq, dk, dv, dgam (1, C), None, ds), all f32. The lines follow `_chunk_fwd`'s backwards."""
    m = first or _chunk_gates(k, gam)
    decay, eg, to_end = m["decay"], m["eg"], m["to_end"]
    qf, kf = q.astype(F32), k.astype(F32)  # for the sums over a row; the products take q, k, v, do as they come
    rows = lambda x: jnp.sum(x, axis=1, keepdims=True)  # noqa: E731
    cols = lambda x: jnp.sum(x, axis=0, keepdims=True)  # noqa: E731
    p = _mm(q, k, NT) * decay
    # s_new = exp(gam_end) s + (k to_end)^T v
    e_end = jnp.exp(m["gam_end"])
    ds = e_end * ds_new
    dv = to_end * _mm(k, ds_new)
    d_kd = _mm(v, ds_new, NT)
    dk = to_end * d_kd
    through_kd = rows(d_kd * kf) * to_end
    dgam_c = -through_kd
    dgam_end = e_end * jnp.sum(rows(ds_new * s), axis=0, keepdims=True) + cols(through_kd)
    # o = eg (q s) + p v
    d_qs = _mm(do, s, NT)
    dq = eg * d_qs
    dgam_c += rows(d_qs * qf) * eg
    ds += _mm(q, eg * do, TN)
    dp = _mm(do, v, NT)
    dv += _mm(p, do, TN)
    # p = (q k^T) decay, on and under the diagonal
    dp_decayed = dp * decay
    dq += _mm(dp_decayed, k)
    dk += _mm(dp_decayed, q, TN)
    through_p = dp * p
    dgam_c += rows(through_p)
    dgam = _row(dgam_c) - cols(through_p) + jnp.where(m["last"], dgam_end, 0.0)
    return dq, dk, dv, dgam, None, ds


def mxu_passes(chunk: int, dk: int, dv: int, dtype, backward: bool = False) -> float:
    """MXU passes of 128^3 multiply-adds the kernels issue for one chunk of one head with q, k, v (and do) of
    `dtype`: six for a product of two f32 arrays, three where one operand is bf16, one where both are."""
    operands, one_cast = (1, 3) if jnp.dtype(dtype) == BF16 else (6, 6)
    unit = 128 ** 3
    ccd, cdd, ccv = chunk * chunk * dk / unit, chunk * dk * dv / unit, chunk * chunk * dv / unit
    passes = operands * ccd  # Q K^T
    if not backward:
        return passes + one_cast * (2 * cdd + ccv)  # Q S, K^T V; P V
    # K dS', V dS'^T, dO S^T, Q^T dO; P^T dO; dP K, dP^T Q; dO V^T
    return passes + one_cast * (4 * cdd + ccv + 2 * ccd) + operands * ccv


def chunk_flops(chunk: int, dk: int, dv: int, dtype, backward: bool = False) -> int:
    """Multiply-adds (2 FLOP each) the kernels issue for one chunk of one head, a product counted once for every
    MXU pass it takes (`mxu_passes`): what XLA is told."""
    return int(2 * 128 ** 3 * mxu_passes(chunk, dk, dv, dtype, backward))


RULE = Rule(name="ssd", kernels="ssd", gate_a_channel=False,
            functions=lambda: (_chunk_gates, _chunk_fwd, _chunk_bwd),
            chunk_flops=chunk_flops, transcendentals=lambda chunk, dk: chunk * chunk,
            inverse=False, max_heads=HEADS)


def ssd_scan(c, b, v, g, mesh=None, *, chunk: int = CHUNK, backend: Optional[str] = None, interpret: bool = False):
    """o (B, H, S, P), in v's type: `o_t = S_t^T c_t`, `S_t = exp(g_t) S_{t-1} + b_t v_t^T`, the walk applied to `RULE`.
    c, b: (B, groups, S, N), groups a divisor of H; v: (B, H, S, P); g (log decay, <= 0): (B, H, S) f32."""
    return chunked_scan(RULE, c, b, v, g, None, mesh, chunk=chunk, backend=backend, interpret=interpret)


def _gates(x, dt, a_log):
    """(the log decay g = dt A (B, H, S) f32, v = dt x in x's type) of a mixer's x, dt and `A_log`."""
    with jax.named_scope("ssd_gates"):
        dt = dt.astype(F32)
        return dt * -jnp.exp(a_log.astype(F32))[None, :, None], (dt[..., None] * x).astype(x.dtype)


def ssd(x, b, c, dt, a_log, d, mesh=None, *, chunk: int = CHUNK, backend: Optional[str] = None,
        interpret: bool = False):
    """y (B, H, S, P), in x's type, of the recurrence at the top of the file (`D x` is added in f32 to the scan's
    output, and the sum rounded once more: what the published kernel hands on).

    x: (B, H, S, P); b, c: (B, groups, S, N), a group the `H / groups` consecutive heads; dt (B, H, S) f32, after its
    softplus; a_log, d: (H,), `A = -exp(a_log)`. Every row starts from a zero state. S need not be a whole number
    of chunks.
    backend: "pallas" | "xla" | None (`select_backend` for the platform the computation is compiled for: the mesh's
      where there is one).
    mesh: as `gated_delta_rule(mesh=)`: batch over (data, fsdp), heads over tensor (a group's heads together)."""
    g, v = _gates(x, dt, a_log)
    with jax.named_scope("ssd_scan"):
        o = ssd_scan(c, b, v, g, mesh, chunk=chunk, backend=backend, interpret=interpret)
    return (o.astype(F32) + d.astype(F32)[None, :, None, None] * x).astype(x.dtype)


def state_after(x, b, dt, a_log, *, chunk: int = CHUNK):
    """The states (B, H, N, P) f32 after the rows' last position, as the forward kernel hands a state on: the row
    with one more chunk of no write and no decay behind it, whose starting state the kernel writes out (in interpret
    mode off the TPU). For a check that holds a reference's state beside it; no gradient."""
    g, v = _gates(x, dt, a_log)
    (batch, heads, seq, _), pad = v.shape, chunk + -x.shape[2] % chunk
    along_seq = lambda z: jnp.pad(z, ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (z.ndim - 3))  # noqa: E731
    flat = lambda z: z.reshape(-1, *z.shape[2:])  # noqa: E731
    b, v, g = along_seq(b), along_seq(v), along_seq(g)
    _, states = _call(RULE, False, (flat(b), flat(b), flat(v), flat(_running_sum(g, chunk)), None), chunk,
                      jax.default_backend() != "tpu")
    return states[:, -1].reshape(batch, heads, *states.shape[2:])
