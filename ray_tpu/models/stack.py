"""Shared transformer-stack scaffolding: scan-over-layers with remat, and the
pipeline-parallel path — one implementation for every model family (GPT,
Llama, ...), so parallelism semantics cannot drift between models.

A model supplies `block_fn(x, (layer_params, idx)) -> (x, aux)`; this module
handles: lax.scan over stacked layer params, jax.checkpoint remat, and — when
the mesh has pipeline > 1 — the GPipe microbatch schedule with optional
in-region ring attention (parallel/pipeline.py).
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp


def apply_stack(
    blocks,  # stacked per-layer params, leading dim n_layer
    x,  # (B, S, D)
    make_block_fn: Callable,  # (first_layer, attention_fn, mb_idx, seq_streams) -> block_fn
    *,
    n_layer: int,
    attention_fn: Optional[Callable],
    mesh=None,
    num_microbatches: Optional[int] = None,
    seq_streams: tuple = (),
) -> Tuple[Any, Any]:
    """Returns (activations, aux_sum). `make_block_fn` mirrors the model's
    per-block computation (dropout RNG handling included) and must already
    wrap remat if the config asks for it. `seq_streams` are per-position
    arrays (leading dim S, e.g. RoPE cos/sin tables) that shard with the
    sequence under context parallelism — inside the pipeline's manual region
    each rank receives its own slice, so global positions stay correct."""
    B = x.shape[0]
    n_pipeline = int(mesh.shape.get("pipeline", 1)) if mesh is not None else 1
    if n_pipeline > 1:
        from ray_tpu.parallel.pipeline import pipeline_apply, to_stages

        # Combining PP with CP: the pipeline region is manual over `pipeline`,
        # so context parallelism joins the same region with the in-region ring
        # attention (a nested full shard_map can't reopen a mesh axis).
        n_context = int(mesh.shape.get("context", 1))
        context_manual = n_context > 1
        inner_attn = attention_fn
        if context_manual:
            import functools

            from ray_tpu.parallel.ring_attention import ring_attention

            inner_attn = functools.partial(ring_attention, axis_name="context")

        def stack_fn(stage_local, xm, first_layer, mb_idx, streams):
            n_local = n_layer // n_pipeline
            xm, auxs = jax.lax.scan(
                make_block_fn(first_layer, inner_attn, mb_idx, streams),
                xm,
                (stage_local, jnp.arange(n_local)),
            )
            return xm, jnp.sum(auxs)

        M = num_microbatches or (2 * n_pipeline if B % (2 * n_pipeline) == 0 else n_pipeline)
        with jax.named_scope("blocks"):
            return pipeline_apply(
                mesh, to_stages(blocks, n_pipeline), x, stack_fn, M,
                context_manual=context_manual,
                seq_streams=seq_streams,
            )
    with jax.named_scope("blocks"):
        x, auxs = jax.lax.scan(
            make_block_fn(0, attention_fn, None, seq_streams),
            x,
            (blocks, jnp.arange(n_layer)),
        )
        return x, jnp.sum(auxs)


def resolve_attention(q, k, v, attention_mode: str, attention_fn: Optional[Callable],
                      mesh=None):
    """One attention-backend dispatch for every model family: caller-injected
    fn (ring/Ulysses wrappers) wins; "xla" forces the plain-XLA form; "auto"
    and "flash" take `flash_attention`'s own choice for this platform and
    shape (`ops.flash_attention.select_backend` says which), with the kernel
    partitioned over `mesh`."""
    if attention_fn is not None:
        return attention_fn(q, k, v)
    from ray_tpu.ops.flash_attention import flash_attention, xla_attention

    if attention_mode == "xla":
        return xla_attention(q, k, v, causal=True)
    if mesh is not None and int(mesh.shape.get("pipeline", 1)) > 1:
        # Inside the pipeline's manual region a second shard_map cannot
        # reopen the mesh; the kernel runs unpartitioned there.
        mesh = None
    return flash_attention(q, k, v, causal=True, mesh=mesh)


def causal_lm_loss(logits, targets):
    """Fused cross entropy: logsumexp - logit[target], one reduction over V
    instead of materializing the (B, S, V) log-softmax (saves ~2x V-sized HBM
    traffic)."""
    with jax.named_scope("loss"):
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        at_target = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        return (lse - at_target).mean()
