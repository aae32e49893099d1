"""Ring attention: context-parallel exact attention over the "context" mesh axis.

Sequence length S is sharded S/cp per device. Each device keeps its Q shard and
rotates K/V shards around the ring with `lax.ppermute` (ICI neighbor links),
folding each incoming block into an online-softmax accumulator — O(S/cp) memory
per device, exact results, overlappable comm/compute. This is the long-context
capability SURVEY.md §5 calls out as absent from the reference ("SP: NO — must
be designed fresh").

`ring_attention` is written to run *inside* shard_map (it uses the axis name);
`ring_attention_sharded` wraps it for a (batch, heads, seq, head_dim) global
array on a mesh with a "context" axis. Alternative head-sharded (Ulysses /
all-to-all) attention is `ulysses_attention` below.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def ring_attention(
    q,
    k,
    v,
    axis_name: str = "context",
    axis_size: Optional[int] = None,
    causal: bool = True,
    sm_scale: Optional[float] = None,
):
    """Exact attention with K/V rotating around the `axis_name` ring.

    Args (per-device shards): q, k, v of shape (batch, heads, s_local, head_dim).
    Must be called inside shard_map/jit over a mesh containing `axis_name`.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if axis_size is None:
        axis_size = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    b, h, s_local, d = q.shape

    qf = q.astype(jnp.float32)
    m0 = jnp.full((b, h, s_local), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_local), jnp.float32)
    acc0 = jnp.zeros((b, h, s_local, d), jnp.float32)

    # After `step` rotations each device holds the K/V shard that originated at
    # (my - step) mod n: perm sends shard i -> i+1 each step.
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def step_fn(carry, step):
        m_prev, l_prev, acc, k_cur, v_cur = carry
        src = jax.lax.rem(my - step + axis_size, axis_size)

        def attend(args):
            m_prev, l_prev, acc, k_cur, v_cur = args
            s = jnp.einsum(
                "bhqd,bhkd->bhqk", qf, k_cur.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            ) * sm_scale
            if causal:
                row = my * s_local + jax.lax.broadcasted_iota(jnp.int32, (s_local, s_local), 0)
                col = src * s_local + jax.lax.broadcasted_iota(jnp.int32, (s_local, s_local), 1)
                s = jnp.where((row >= col)[None, None], s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p, v_cur.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )
            return m_new, l_new, acc_new

        if causal:
            # K/V blocks entirely in this device's future contribute nothing:
            # skip the quadratic compute (the branch condition is identical on
            # every device for a given step, so control flow stays uniform).
            m_new, l_new, acc_new = jax.lax.cond(
                src > my,
                lambda args: (args[0], args[1], args[2]),
                attend,
                (m_prev, l_prev, acc, k_cur, v_cur),
            )
        else:
            m_new, l_new, acc_new = attend((m_prev, l_prev, acc, k_cur, v_cur))
        # Rotate K/V to the next device; XLA overlaps this with the next step's
        # compute when it can (double-buffered ring).
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        return (m_new, l_new, acc_new, k_next, v_next), None

    (m, l, acc, _, _), _ = jax.lax.scan(
        step_fn, (m0, l0, acc0, k, v), jnp.arange(axis_size)
    )
    l = jnp.maximum(l, 1e-30)
    return (acc / l[..., None]).astype(q.dtype)


def ring_attention_sharded(mesh, q, k, v, causal: bool = True, sm_scale: Optional[float] = None):
    """shard_map wrapper: global (batch, heads, seq, head_dim) arrays with seq
    sharded over the mesh's "context" axis, batch over (data, fsdp)."""
    from jax.sharding import PartitionSpec as P

    axis_size = mesh.shape["context"]
    spec = P(("data", "fsdp"), None, "context", None)
    fn = jax.shard_map(
        functools.partial(
            ring_attention,
            axis_name="context",
            axis_size=axis_size,
            causal=causal,
            sm_scale=sm_scale,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


def ulysses_attention(
    q,
    k,
    v,
    axis_name: str = "context",
    axis_size: Optional[int] = None,
    causal: bool = True,
    sm_scale: Optional[float] = None,
):
    """Ulysses-style sequence parallelism: all-to-all swaps the sharded axis
    from sequence to heads, each device runs full-sequence attention for its
    head subset, then all-to-all swaps back. Cheaper than ring when
    heads >= axis_size; requires heads % axis_size == 0.

    Call inside shard_map with q/k/v sharded (batch, heads, seq/cp, head_dim).
    """
    from ray_tpu.ops.flash_attention import xla_attention

    if axis_size is None:
        axis_size = jax.lax.psum(1, axis_name)
    b, h, s_local, d = q.shape

    def seq_to_heads(x):
        # (b, h, s/cp, d) -> all-to-all -> (b, h/cp, s, d)
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    oh = xla_attention(qh, kh, vh, causal=causal, sm_scale=sm_scale)
    return heads_to_seq(oh)
