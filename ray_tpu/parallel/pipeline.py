"""Pipeline parallelism: GPipe-style microbatch schedule over the `pipeline`
mesh axis, SPMD-native.

No reference equivalent (SURVEY.md §2: PP "NO in-tree", only external Alpa,
`/root/reference/release/alpa_tests/train_opt_2_7b_minimum.py`); this is the
TPU-first design the blueprint (§7 step 8) calls for:

 - layer stacks are sharded over `pipeline` on their leading (stage) dim, so
   each device group stores only L/P layers — the memory win PP exists for;
 - only the `pipeline` axis is manual (`shard_map(axis_names={"pipeline"})`);
   data/fsdp/tensor/context stay compiler-managed, so TP/DP/CP collectives are
   still inserted by XLA *inside* each stage;
 - activations advance between stages with `lax.ppermute` over ICI; the
   backward pass pipelines automatically because ppermute/scan transpose to the
   reversed schedule;
 - schedule: M microbatches through P stages in M+P-1 ticks (bubble fraction
   (P-1)/(M+P-1); raise `num_microbatches` to amortize it).

All ranks run every tick (SPMD): ticks where a rank has no real microbatch
compute garbage that is masked out of the result — that idle-compute IS the
pipeline bubble, made explicit.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(
    mesh,
    stage_params,
    x,
    block_stack_fn: Callable,
    num_microbatches: int,
    context_manual: bool = False,
    seq_streams: tuple = (),
):
    """Run `block_stack_fn(stage_params_local, x_mb, first_layer_idx)` as a
    P-stage pipeline over microbatches of `x`.

    Args:
      mesh: jax Mesh with a `pipeline` axis of size P > 1.
      stage_params: pytree whose leaves have leading dim P (stage), i.e. layer
        stacks reshaped (L, ...) -> (P, L//P, ...), sharded over `pipeline`.
      x: (B, S, D) activations (embedded tokens).
      block_stack_fn: applies one stage's layer stack to one microbatch:
        (local_params with leading dim L//P, (mb, S, D), first_layer_idx,
        microbatch_idx, seq_streams) -> ((mb, S, D), aux_scalar). The
        microbatch index keeps per-microbatch randomness (dropout)
        independent, matching non-pipelined semantics; aux (e.g. MoE
        load-balance loss) accumulates over REAL ticks only (bubble-tick
        garbage is masked out), summed over stages via psum and averaged over
        microbatches.
      seq_streams: per-position arrays with leading dim S (e.g. RoPE cos/sin
        tables) that must shard with the sequence: inside the region each rank
        sees its context shard, keeping GLOBAL positions correct under CP.
      num_microbatches: M; must divide B.
      context_manual: also make the `context` axis manual inside the pipeline
        region (sequence dim sharded S/cp per rank) so ring attention — which
        runs collectives over the context axis name — can execute inside the
        stage. Required when combining PP with CP: a nested full shard_map
        cannot open a second manual region over an axis of the same mesh.

    Returns ((B, S, D) activations after all L layers, aux scalar), both
    replicated over the pipeline axis (final psum-mask), so the LM head /
    loss can be computed with ordinary auto-sharded ops.
    """
    Pp = mesh.shape["pipeline"]
    B, S, D = x.shape
    M = num_microbatches
    if B % M != 0:
        raise ValueError(f"num_microbatches={M} must divide batch {B}")
    x_mb = x.reshape(M, B // M, S, D)

    def per_rank(stage_local, x_all, *streams):
        # stage_local leaves: (1, L//P, ...) — this rank's stage slice.
        stage_local = jax.tree.map(lambda a: a[0], stage_local)
        p = jax.lax.axis_index("pipeline")
        n_local = jax.tree.leaves(stage_local)[0].shape[0]
        first_layer = p * n_local
        T = M + Pp - 1

        def tick(carry, t):
            buf, out, aux_acc = carry
            mb_idx = jnp.clip(t, 0, M - 1)
            inject = jax.lax.dynamic_index_in_dim(x_all, mb_idx, 0, keepdims=False)
            # Stage 0 feeds fresh microbatches; later stages consume what the
            # previous stage ppermuted over last tick.
            x_in = jnp.where(p == 0, inject, buf)
            # The microbatch this rank is processing at tick t.
            mb_proc = jnp.clip(t - p, 0, M - 1)
            y, aux = block_stack_fn(stage_local, x_in, first_layer, mb_proc, streams)
            # Bubble ticks compute garbage: only real (stage, microbatch)
            # pairs contribute aux.
            real = jnp.logical_and(t - p >= 0, t - p < M)
            aux_acc = aux_acc + jnp.where(real, aux, 0.0)
            # Last stage banks finished microbatch t-(P-1), other ticks/ranks
            # write back the value already there (masked no-op).
            out_idx = jnp.clip(t - (Pp - 1), 0, M - 1)
            cur = jax.lax.dynamic_index_in_dim(out, out_idx, 0, keepdims=False)
            valid = jnp.logical_and(p == Pp - 1, t >= Pp - 1)
            out = jax.lax.dynamic_update_index_in_dim(
                out, jnp.where(valid, y, cur), out_idx, 0
            )
            buf = jax.lax.ppermute(
                y, "pipeline", [(i, (i + 1) % Pp) for i in range(Pp)]
            )
            return (buf, out, aux_acc), None

        buf0 = jnp.zeros_like(x_all[0])
        out0 = jnp.zeros_like(x_all)
        aux0 = jnp.zeros((), jnp.float32)
        (_, out, aux_acc), _ = jax.lax.scan(tick, (buf0, out0, aux0), jnp.arange(T))
        # Replicate the last stage's results across the pipeline axis; sum
        # stage aux contributions (each stage owns distinct layers).
        out = jax.lax.psum(jnp.where(p == Pp - 1, out, jnp.zeros_like(out)), "pipeline")
        aux_total = jax.lax.psum(aux_acc, "pipeline") / M
        return out, aux_total

    manual = {"pipeline"}
    x_spec = P()
    stream_spec = P()
    if context_manual:
        manual.add("context")
        # x_mb is (M, mb, S, D): shard the sequence dim over context; streams
        # shard their leading (position) dim the same way.
        x_spec = P(None, None, "context", None)
        stream_spec = P("context")
    sharded = jax.shard_map(
        per_rank,
        mesh=mesh,
        in_specs=(P("pipeline"), x_spec) + (stream_spec,) * len(seq_streams),
        out_specs=(x_spec, P()),
        axis_names=frozenset(manual),
        check_vma=False,
    )
    out, aux = sharded(stage_params, x_mb, *seq_streams)
    return out.reshape(B, S, D), aux


def to_stages(blocks, num_stages: int):
    """Reshape stacked layer params (L, ...) -> (num_stages, L//num_stages, ...)."""

    def split(a):
        L = a.shape[0]
        if L % num_stages != 0:
            raise ValueError(f"n_layer={L} not divisible by pipeline={num_stages}")
        return a.reshape(num_stages, L // num_stages, *a.shape[1:])

    return jax.tree.map(split, blocks)
