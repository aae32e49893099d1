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
kernels `gdn_fwd` and `gdn_bwd` call the same two functions on their blocks, a
head a program, the chunks along a sequential grid axis with the state (`dS`
in the reverse walk) in VMEM scratch. The forward kernel writes out the state
every chunk starts from, (B, H, S / C, d_k, d_v) f32, for the backward pass,
which makes `T` and `N` again. Every product that touches the state, the decay
or `T` is f32 x f32 at full precision; only `K K^T` and `Q K^T` multiply the
operands as they come (bf16 in a bf16 model, f32 accumulation).
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
F32 = jnp.float32
NT = (((1,), (1,)), ((), ()))  # a @ b.T
NN = (((1,), (0,)), ((), ()))  # a @ b
TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _mm(a, b, dims=NN):
    """a . b with f32 accumulation; at full f32 precision where both are f32."""
    exact = a.dtype == F32 and b.dtype == F32
    return jax.lax.dot_general(a, b, dims, preferred_element_type=F32,
                               precision=jax.lax.Precision.HIGHEST if exact else None)


def _iotas(n: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0), jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))


def _col(row):
    """(1, C) -> (C, 1) by the diagonal of its broadcast: no transpose of a one-row tile."""
    r, c = _iotas(row.shape[1])
    return jnp.sum(jnp.where(r == c, row, 0.0), axis=1, keepdims=True)


def _row(col):
    r, c = _iotas(col.shape[0])
    return jnp.sum(jnp.where(r == c, col, 0.0), axis=0, keepdims=True)


def _unit_lower_inverse(a):
    """(I + a)^-1 for a strictly lower triangular (C, C), C a power of two, by
    doubling the block size of the block diagonal's inverse."""
    n = a.shape[0]
    r, c = _iotas(n)
    joins = lambda level: jnp.where(((r ^ c) >> level) == 1, a, 0.0)  # noqa: E731  (same block of 2b, other half of it)
    x = (r == c).astype(F32) - joins(0)  # blocks of 2: X - X E X with X the identity
    level = 1
    while (1 << level) < n:
        x = x - _mm(x, _mm(joins(level), x))
        level += 1
    return x


def _chunk_parts(q, k, v, gam, beta, s):
    """What the forward and the backward pass of a chunk both need."""
    n = k.shape[0]
    r, c = _iotas(n)
    gam_c, beta_c = _col(gam), _col(beta)
    decay = jnp.exp(jnp.where(r >= c, gam_c - gam, -jnp.inf))  # D: 0 above the diagonal, 1 on it
    a = jnp.where(r > c, beta_c * _mm(k, k, NT) * decay, 0.0)
    t = _unit_lower_inverse(a)
    p = _mm(q, k, NT) * decay
    qf, kf, vf = q.astype(F32), k.astype(F32), v.astype(F32)
    eg = jnp.exp(gam_c)
    ks = _mm(kf, s)
    z = vf - eg * ks
    new = _mm(t, beta_c * z)  # N
    last = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1) == n - 1
    gam_end = jnp.sum(jnp.where(last, gam, 0.0), axis=1, keepdims=True)  # (1, 1)
    to_end = jnp.exp(gam_end - gam_c)
    return dict(r=r, c=c, gam_c=gam_c, beta_c=beta_c, decay=decay, a=a, t=t, p=p, qf=qf, kf=kf, eg=eg,
                ks=ks, z=z, new=new, gam_end=gam_end, to_end=to_end, last=last)


def _chunk_fwd(q, k, v, gam, beta, s):
    """One chunk of one head: q, k (C, d_k), v (C, d_v), `gam` the running sum
    of g inside the chunk and `beta` as rows (1, C) f32, `s` (d_k, d_v) f32 the
    state before it. Returns (o (C, d_v) f32, the state after it)."""
    m = _chunk_parts(q, k, v, gam, beta, s)
    o = _mm(m["qf"] * m["eg"], s) + _mm(m["p"], m["new"])
    s_new = jnp.exp(m["gam_end"]) * s + _mm(m["kf"] * m["to_end"], m["new"], TN)
    return o, s_new


def _chunk_bwd(q, k, v, gam, beta, s, do, ds_new):
    """The chunk's vector-Jacobian product: from `do` (C, d_v) and the
    cotangent `ds_new` of the state after the chunk to (dq, dk, dv, dgam (1, C),
    dbeta (1, C), ds), all f32. The lines follow `_chunk_fwd`'s backwards."""
    m = _chunk_parts(q, k, v, gam, beta, s)
    r, c, decay, a, t, p = m["r"], m["c"], m["decay"], m["a"], m["t"], m["p"]
    qf, kf, eg, beta_c, ks, new = m["qf"], m["kf"], m["eg"], m["beta_c"], m["ks"], m["new"]
    do = do.astype(F32)
    rows = lambda x: jnp.sum(x, axis=1, keepdims=True)  # noqa: E731
    cols = lambda x: jnp.sum(x, axis=0, keepdims=True)  # noqa: E731
    # s_new = exp(gam_end) s + (k to_end)^T new
    e_end, kd = jnp.exp(m["gam_end"]), kf * m["to_end"]
    ds = e_end * ds_new
    d_new = _mm(kd, ds_new)
    d_kd = _mm(new, ds_new, NT)
    dk = m["to_end"] * d_kd
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
    dq += _mm(dp_decayed, kf)
    dk += _mm(dp_decayed, qf, TN)
    through_p = dp * p
    dgam_c += rows(through_p)
    dgam_r = -cols(through_p)
    # new = (I + a)^-1 (beta z)
    dr = _mm(t, d_new, TN)
    da = -_mm(dr, new, NT)
    # a = beta_i (k_i . k_j) decay, under the diagonal
    da_decayed = jnp.where(r > c, da * decay, 0.0)
    d_kb = _mm(da_decayed, kf)
    dk += _mm(da_decayed, beta_c * kf, TN) + beta_c * d_kb
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
    ds += _mm(kf, d_ks, TN)
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
def _fwd_kernel(q_ref, k_ref, v_ref, gam_ref, beta_ref, o_ref, states_ref, s_ref):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    s = s_ref[...]
    states_ref[0, 0] = s
    o, s_new = _chunk_fwd(q_ref[0], k_ref[0], v_ref[0], gam_ref[0, pl.ds(i, 1), :],
                          beta_ref[0, pl.ds(i, 1), :], s)
    o_ref[0] = o.astype(o_ref.dtype)
    s_ref[...] = s_new


def _bwd_kernel(q_ref, k_ref, v_ref, gam_ref, beta_ref, states_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dgam_ref, dbeta_ref, ds_ref):
    i = pl.program_id(1)
    at = pl.num_programs(1) - 1 - i  # the chunk: the walk is from the row's end

    @pl.when(i == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    dq, dk, dv, dgam, dbeta, ds = _chunk_bwd(
        q_ref[0], k_ref[0], v_ref[0], gam_ref[0, pl.ds(at, 1), :], beta_ref[0, pl.ds(at, 1), :],
        states_ref[0, 0], do_ref[0], ds_ref[...])
    dq_ref[0] = dq.astype(dq_ref.dtype)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)
    dgam_ref[0, pl.ds(at, 1), :] = dgam
    dbeta_ref[0, pl.ds(at, 1), :] = dbeta
    ds_ref[...] = ds


def chunk_flops(chunk: int, dk: int, dv: int, backward: bool = False) -> int:
    """Products the kernels issue for one chunk of one head (2 a multiply-add),
    the doubling's `2 (log2 C - 1)` products of C^3 included: what XLA is told."""
    levels = chunk.bit_length() - 2
    common = 2 * chunk * chunk * dk * 2 + 2 * levels * 2 * chunk ** 3 + 2 * chunk * dk * dv + 2 * chunk * chunk * dv
    if not backward:
        return common + 2 * chunk * dk * dv * 2 + 2 * chunk * chunk * dv
    return common + 2 * chunk * dk * dv * 6 + 2 * chunk * chunk * dv * 4 + 2 * chunk * chunk * dk * 4


def _compiler_params(interpret):
    return None if interpret else pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def _gates_by_chunk(x, chunk: int):
    bh, s = x.shape
    return x.reshape(bh, s // chunk, chunk)


def _fwd(q, k, v, gam, beta, chunk, interpret):
    """Flat heads: q, k (BH, S, d_k), v (BH, S, d_v), gam, beta (BH, S) f32."""
    bh, seq, dk = k.shape
    dv, n = v.shape[-1], seq // chunk
    per_chunk = lambda d: pl.BlockSpec((1, chunk, d), lambda h, i: (h, i, 0))  # noqa: E731
    per_head = pl.BlockSpec((1, n, chunk), lambda h, i: (h, 0, 0))
    with jax.named_scope(f"chunk_{chunk}"):
        return pl.pallas_call(
            _fwd_kernel,
            grid=(bh, n),
            in_specs=[per_chunk(dk), per_chunk(dk), per_chunk(dv), per_head, per_head],
            out_specs=[per_chunk(dv), pl.BlockSpec((1, 1, dk, dv), lambda h, i: (h, i, 0, 0))],
            out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                       jax.ShapeDtypeStruct((bh, n, dk, dv), F32)],
            scratch_shapes=[pltpu.VMEM((dk, dv), F32)],
            interpret=interpret,
            name="gdn_fwd",
            compiler_params=_compiler_params(interpret),
            cost_estimate=pl.CostEstimate(
                flops=bh * n * chunk_flops(chunk, dk, dv),
                bytes_accessed=bh * (seq * (2 * dk + 2 * dv) * q.dtype.itemsize + n * dk * dv * 4 + 2 * seq * 4),
                transcendentals=bh * n * chunk * chunk),
        )(q, k, v, _gates_by_chunk(gam, chunk), _gates_by_chunk(beta, chunk))


def _bwd(q, k, v, gam, beta, states, do, chunk, interpret):
    bh, seq, dk = k.shape
    dv, n = v.shape[-1], seq // chunk
    per_chunk = lambda d: pl.BlockSpec((1, chunk, d), lambda h, i: (h, n - 1 - i, 0))  # noqa: E731
    per_head = pl.BlockSpec((1, n, chunk), lambda h, i: (h, 0, 0))
    gates = jax.ShapeDtypeStruct((bh, n, chunk), F32)
    with jax.named_scope(f"chunk_{chunk}"):
        dq, dk_, dv_, dgam, dbeta = pl.pallas_call(
            _bwd_kernel,
            grid=(bh, n),
            in_specs=[per_chunk(dk), per_chunk(dk), per_chunk(dv), per_head, per_head,
                      pl.BlockSpec((1, 1, dk, dv), lambda h, i: (h, n - 1 - i, 0, 0)), per_chunk(dv)],
            out_specs=[per_chunk(dk), per_chunk(dk), per_chunk(dv), per_head, per_head],
            out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct(k.shape, k.dtype),
                       jax.ShapeDtypeStruct(v.shape, v.dtype), gates, gates],
            scratch_shapes=[pltpu.VMEM((dk, dv), F32)],
            interpret=interpret,
            name="gdn_bwd",
            compiler_params=_compiler_params(interpret),
            cost_estimate=pl.CostEstimate(
                flops=bh * n * chunk_flops(chunk, dk, dv, backward=True),
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
