"""Flash attention for TPU as Pallas kernels (forward + backward), with an XLA
form for non-TPU backends (`select_backend` says which one a call runs).

Design (pallas_guide.md playbook):
 - forward: grid over (batch*heads, q_blocks); K/V rows for the (b,h) pair live
   in VMEM; online-softmax accumulation in fp32 over K blocks (fori_loop, no
   dynamic Python control flow); causal masking prunes future K blocks via the
   loop bound, and the diagonal block via broadcasted_iota row/col ids.
 - backward: ONE fused kernel per (batch*heads) computing dk/dv blockwise and
   accumulating dq in a VMEM scratch across the sequential K-block grid dim —
   s/p are recomputed once per (q,k) block pair instead of twice (the classic
   two-kernel split recomputes them in both the dq and dkv kernels).
   O(seq) memory, the point of flash attention.
 - matmuls run on the MXU with preferred_element_type=float32; inputs can be
   bfloat16.

The reference repo has no attention kernels at all (it is a distributed-systems
layer); this file exists because long-context is first-class in the TPU build
(SURVEY.md §5 "long-context... designed fresh").
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# One 1024x1024 block per (batch, head) at GPT-2's sequence length: fewer grid
# steps and loop iterations, more MXU work per step to amortize the
# online-softmax vector ops. Against 512x512 through the full train step on
# v5e: not measured (no driver record; PERF.md Finding 6). Blocks are capped
# to seq_len at call time, so short sequences still get valid (smaller) blocks.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
NEG_INF = -1e30


# --------------------------------------------------------------------------- XLA form
def xla_attention(q, k, v, causal: bool = True, sm_scale: Optional[float] = None):
    """Plain-XLA attention (fused well by the compiler; O(S^2) memory)."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * sm_scale
    if causal:
        qlen, klen = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((qlen, klen), dtype=bool), k=klen - qlen)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, preferred_element_type=jnp.float32).astype(q.dtype)


# --------------------------------------------------------------------------- forward kernel
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale, causal, block_q, block_k, seq_len):
    qi = pl.program_id(1)
    # Matmul operands stay in their input dtype (bf16 in training): f32x f32
    # dots run the MXU at a fraction of its bf16 rate; accumulation is f32 via
    # preferred_element_type either way. sm_scale folds into q once (block_q x d)
    # instead of rescaling every (block_q x block_k) score matrix.
    q = (q_ref[0].astype(jnp.float32) * sm_scale).astype(q_ref.dtype)  # (block_q, d)

    num_k_blocks = pl.cdiv(seq_len, block_k)
    if causal:
        # Future K blocks contribute nothing: stop after the diagonal block.
        hi = jax.lax.div((qi + 1) * block_q + block_k - 1, block_k)
        hi = jnp.minimum(hi, num_k_blocks)
    else:
        hi = num_k_blocks

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, q.shape[-1]), jnp.float32)

    def make_body(masked):
        def body(j, carry):
            m_prev, l_prev, acc = carry
            k = k_ref[0, pl.ds(j * block_k, block_k), :]
            v = v_ref[0, pl.ds(j * block_k, block_k), :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )  # (block_q, block_k)
            if masked:
                row = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
                col = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
                s = jnp.where(row >= col, s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[:, None])
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[:, None] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return m_new, l_new, acc

        return body

    if causal:
        # K blocks strictly below the diagonal need no mask (row >= col always
        # holds); only blocks intersecting the diagonal pay the iota/where.
        lo_diag = jax.lax.div(qi * block_q, block_k)  # first block that may mask
        carry = jax.lax.fori_loop(0, lo_diag, make_body(False), (m0, l0, acc0))
        m, l, acc = jax.lax.fori_loop(lo_diag, hi, make_body(True), carry)
    else:
        m, l, acc = jax.lax.fori_loop(0, hi, make_body(False), (m0, l0, acc0))
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l[:, None]).astype(o_ref.dtype)
    lse_ref[0] = (m + jnp.log(l))[:, None]


def _fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    bh, seq, d = q.shape
    grid = (bh, pl.cdiv(seq, block_q))
    out_shape = [
        jax.ShapeDtypeStruct((bh, seq, d), q.dtype),
        # (bh, seq, 1): TPU block specs constrain the last two dims, so the
        # per-row stats carry a trailing unit dim to stay tileable.
        jax.ShapeDtypeStruct((bh, seq, 1), jnp.float32),
    ]
    kernel = functools.partial(
        _fwd_kernel,
        sm_scale=sm_scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        seq_len=seq,
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, seq, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, seq, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=out_shape,
        interpret=interpret,
        name="flash_fwd",
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * seq * seq * d,
            bytes_accessed=3 * seq * d * q.dtype.itemsize + seq * d * q.dtype.itemsize,
            transcendentals=seq * seq,
        ),
    )(q, k, v)
    return o, lse


# --------------------------------------------------------------------------- backward kernel
def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dq_acc, *,
                      sm_scale, causal, block_q, block_k, seq_len):
    """Grid (bh, kj) with kj sequential: per K block, loop Q blocks computing
    dk/dv directly; dq contributions accumulate in the f32 VMEM scratch
    (seq, d) that lives across the kj steps of one (b,h) pair."""
    kj = pl.program_id(1)
    num_k_blocks = pl.cdiv(seq_len, block_k)
    k = k_ref[0]  # (block_k, d)
    v = v_ref[0]

    @pl.when(kj == 0)
    def _zero():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    num_q_blocks = pl.cdiv(seq_len, block_q)
    lo = jax.lax.div(kj * block_k, block_q) if causal else 0

    def make_body(masked):
        def body(i, carry):
            dk, dv = carry
            q = q_ref[0, pl.ds(i * block_q, block_q), :]
            do = do_ref[0, pl.ds(i * block_q, block_q), :]
            lse = lse_ref[0, pl.ds(i * block_q, block_q), 0]
            delta = delta_ref[0, pl.ds(i * block_q, block_q), 0]
            qs = (q.astype(jnp.float32) * sm_scale).astype(q.dtype)
            s = jax.lax.dot_general(
                qs, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )  # (block_q, block_k)
            if masked:
                row = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
                col = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
                s = jnp.where(row >= col, s, NEG_INF)
            p = jnp.exp(s - lse[:, None])
            dv = dv + jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            ds = (p * (dp - delta[:, None]) * sm_scale).astype(q.dtype)
            dk = dk + jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            sl = pl.ds(i * block_q, block_q)
            dq_acc[sl, :] = dq_acc[sl, :] + jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            return dk, dv

        return body

    dk0 = jnp.zeros((block_k, k.shape[-1]), jnp.float32)
    dv0 = jnp.zeros((block_k, v.shape[-1]), jnp.float32)
    if causal:
        # Q blocks past the diagonal band see this K block in full (row >= col
        # for every pair): no mask needed there.
        hi_diag = jnp.minimum(
            jax.lax.div((kj + 1) * block_k + block_q - 1, block_q), num_q_blocks
        )
        dk, dv = jax.lax.fori_loop(lo, hi_diag, make_body(True), (dk0, dv0))
        dk, dv = jax.lax.fori_loop(hi_diag, num_q_blocks, make_body(False), (dk, dv))
    else:
        dk, dv = jax.lax.fori_loop(lo, num_q_blocks, make_body(False), (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)

    @pl.when(kj == num_k_blocks - 1)
    def _flush_dq():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd(causal, sm_scale, block_q, block_k, interpret, res, g):
    q, k, v, o, lse = res
    do = g
    bh, seq, d = q.shape
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)[..., None]  # (bh, seq, 1)

    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_fused_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, seq_len=seq,
        ),
        grid=(bh, pl.cdiv(seq, block_k)),
        in_specs=[
            pl.BlockSpec((1, seq, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, seq, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, seq, 1), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, seq, 1), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[
            # dq is revisited every kj step (index map constant in j) and
            # flushed once per (b,h) when the grid moves on.
            pl.BlockSpec((1, seq, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, seq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, seq, d), q.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((seq, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd",
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# --------------------------------------------------------------------------- blockwise (long-seq XLA)
def blockwise_attention(q, k, v, causal: bool = True, sm_scale: Optional[float] = None,
                        block_k: int = 1024):
    """O(S * block_k)-memory attention as a remat'ed scan over K blocks — the
    long-sequence path while the pallas kernels keep full-seq K/V in VMEM
    (which caps them around S~8k at d=64). Exact, differentiable, pure XLA."""
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
        if causal:
            col = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (S, block_k), 1)
            s = jnp.where((row >= col)[None, None], s, NEG_INF)
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
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_bhsd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    o, _ = _fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret)
    return o


def _flash_fwd_rule(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    o, lse = _fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(causal, sm_scale, block_q, block_k, interpret, res, g):
    return _bwd(causal, sm_scale, block_q, block_k, interpret, res, g)


_flash_bhsd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _kernel_blocks(seq: int, block_q: int, block_k: int):
    """Cap blocks to seq_len, then shrink to a divisor (gcd keeps the largest
    power-of-two factor) so defaults work for any seq that has one — e.g.
    S=1536 uses 512-blocks."""
    return math.gcd(min(block_q, seq), seq), math.gcd(min(block_k, seq), seq)


def select_backend(shape, platform: Optional[str] = None,
                   block_q: int = DEFAULT_BLOCK_Q, block_k: int = DEFAULT_BLOCK_K) -> str:
    """The implementation `flash_attention(backend=None)` runs for q/k/v of
    `shape` (batch, heads, seq, head_dim): "pallas" | "blockwise" | "xla".

    The choice is by platform and shape only, and this function is the one
    place it is made, so a caller can say which path its step compiled
    without reading the HLO: off-TPU the XLA form; on TPU the kernel while
    K/V for one (batch, head) fit in VMEM (~2*S*D bytes in bf16: up to ~8k
    tokens at d=64), the blockwise scan beyond that, and the XLA form for a
    sequence with no block of at least 128 dividing it.
    """
    if (platform or jax.default_backend()) != "tpu":
        return "xla"
    _, _, s, d = shape
    if s * d > 8192 * 64:
        return "blockwise"
    if min(_kernel_blocks(s, block_q, block_k)) < 128:
        return "xla"
    return "pallas"


def flash_attention(
    q,
    k,
    v,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    backend: Optional[str] = None,
    interpret: bool = False,
    mesh=None,
):
    """Multi-head attention, (batch, heads, seq, head_dim) layout.

    backend: "pallas" | "xla" | "blockwise" | None (`select_backend`).
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
        return xla_attention(q, k, v, causal=causal, sm_scale=sm_scale)
    if backend == "blockwise":
        return blockwise_attention(q, k, v, causal=causal, sm_scale=sm_scale)
    block_q, block_k = _kernel_blocks(q.shape[2], block_q, block_k)
    if min(block_q, block_k) < 128:
        raise ValueError(
            f"flash_attention(backend='pallas'): seq_len {q.shape[2]} has no "
            "block of at least 128 dividing it; the kernel cannot tile it"
        )

    def kernel(q, k, v):
        b, h, s, d = q.shape
        flat = lambda x: x.reshape(b * h, s, d)
        o = _flash_bhsd(flat(q), flat(k), flat(v), causal, sm_scale, block_q, block_k, interpret)
        return o.reshape(b, h, s, d)

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
