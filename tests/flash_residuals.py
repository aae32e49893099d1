"""What `ops/flash_attention.py`'s two `custom_vjp`s keep for their backward pass (PR 60): the boundary as it stood
before, rebuilt from the module's own kernels, for `tests/test_flash_attention.py` and `tests/test_flash_pairs.py`
to hold the new one to bit for bit; and a count of what a layer scan stacks. A helper, not a test file."""

import functools
import importlib
import types

import jax
import jax.numpy as jnp

from ray_tpu.models import stack

fa = importlib.import_module("ray_tpu.ops.flash_attention")  # `ray_tpu.ops.flash_attention` is the function


# ------------------------------------------------------------------ the boundary before PR 60
# (batch * heads, seq, d) in and out of both `custom_vjp`s, the reshapes outside them: the rules saved `o` in that
# shape while the caller went on with `o.reshape(b, h, s, d)`, two arrays to jax of one value.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flat_bhsd(q, k, v, causal, sm_scale, plan, interpret):
    return fa._fwd(q, k, v, causal, sm_scale, plan, interpret)[0]


def _flat_bhsd_fwd(q, k, v, causal, sm_scale, plan, interpret):
    o, lse = fa._fwd(q, k, v, causal, sm_scale, plan, interpret)
    return o, (q, k, v, o, lse)


_flat_bhsd.defvjp(_flat_bhsd_fwd, lambda causal, sm_scale, plan, interpret, res, g: fa._bwd(
    causal, sm_scale, plan, interpret, res, g))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flat_pairs(q, k, v, keep, causal, sm_scale, plan, interpret):
    return fa._fwd_pairs(q, k, v, keep, causal, sm_scale, plan, interpret)


def _flat_pairs_fwd(q, k, v, keep, causal, sm_scale, plan, interpret):
    o, lse = fa._fwd_pairs(q, k, v, keep, causal, sm_scale, plan, interpret)
    return (o, lse), (q, k, v, keep, o, lse)


def _flat_pairs_bwd(causal, sm_scale, plan, interpret, res, g):
    q, k, v, keep, o, lse = res
    do = g[0]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)[..., None]
    dq, dk, dv = fa._bwd_pairs(q, k, v, do, lse, delta, causal, sm_scale, plan, interpret, keep)
    group = q.shape[0] // k.shape[0]
    if group > 1:
        dk, dv = (x.reshape(k.shape[0], group, *x.shape[1:]).sum(axis=1, dtype=jnp.float32).astype(x.dtype)
                  for x in (dk, dv))
    return dq, dk, dv, None


_flat_pairs.defvjp(_flat_pairs_fwd, _flat_pairs_bwd)


def attention_before(q, k, v, causal=True, keep=None, return_lse=False, mesh=None, **blocks):
    """`flash_attention(backend="pallas", interpret=True)` with the boundary where it stood: the same plan, the
    same kernels on the same operands, over `mesh` in a `shard_map` round the same function."""
    sm_scale = q.shape[-1] ** -0.5
    pairs = return_lse or fa._streams_pairs(
        q.shape[2], q.shape[3], q.dtype.itemsize, k.shape[1] != q.shape[1], keep is not None, causal)
    plan = fa._kernel_blocks(q.shape[2], q.shape[3], causal, blocks.get("block_q"), blocks.get("block_k"),
                             q.dtype.itemsize, pairs)

    def kernel(q, k, v, *keep):
        b, h, s, d = q.shape
        flat = lambda x: x.reshape(-1, s, d)
        if not pairs:
            return _flat_bhsd(flat(q), flat(k), flat(v), causal, sm_scale, plan, True).reshape(b, h, s, d)
        o, lse = _flat_pairs(flat(q), flat(k), flat(v), keep[0] if keep else None, causal, sm_scale, plan, True)
        return o.reshape(b, h, s, d), jax.lax.stop_gradient(lse).reshape(b, h, s)

    operands = (q, k, v) + (() if keep is None else (keep,))
    if mesh is not None:
        from ray_tpu.parallel import ShardingRules

        rules = ShardingRules()
        spec = rules.mesh_axes(("batch", "heads", None, None), mesh=mesh, shape=q.shape)
        in_specs = (spec, spec, spec) + (() if keep is None else (
            rules.mesh_axes(("batch", None, None), mesh=mesh, shape=keep.shape),))
        out_specs = (spec, jax.sharding.PartitionSpec(*spec[:3])) if pairs else spec
        kernel = jax.shard_map(kernel, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)
    out = kernel(*operands)
    return out if return_lse or not pairs else out[0]


def both_boundaries(q, k, v, do, **call):
    """((o[, lse]), (dq, dk, dv)) of `flash_attention` and of `attention_before` on one call: the output's
    cotangent is `do`, the same for both, so every array of one side has its twin on the other."""
    def run(attn):
        def f(q, k, v):
            out = attn(q, k, v, **call)
            o = out[0] if call.get("return_lse") else out
            return (o.astype(jnp.float32) * do.astype(jnp.float32)).sum(), out
        grads, out = jax.jit(jax.grad(f, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return jax.tree.leaves(out), grads

    now = run(functools.partial(fa.flash_attention, backend="pallas", interpret=True))
    return now, run(attention_before)


# ------------------------------------------------------------------ what a layer scan stacks
def stacked_by_the_forward_scan(attention, q_shape, kv_heads, layers=2, dtype=jnp.bfloat16):
    """The shapes of the arrays the forward scan of `jax.grad` stacks a layer at a time (its outputs beside the
    carry: the residuals), for `layers` of `stack.block` under "save_attn" round `attention(q, k, v)`: `qkv_part` a
    product a stream, `out_part` a product of `o`, both checkpointed by `block`. The jaxpr alone: nothing runs."""
    b, h, s, d = q_shape
    config = types.SimpleNamespace(remat=True, remat_policy="save_attn", attention="flash")

    def qkv_part(x, layer):
        heads = lambda w, n: jnp.einsum("bsd,dnh->bnsh", x, w.astype(dtype)).reshape(b, n, s, d)
        return heads(layer["q"], h), heads(layer["k"], kv_heads), heads(layer["v"], kv_heads)

    def out_part(x, o, layer, rng):
        return x + jnp.einsum("bnsh,nhd->bsd", o, layer["o"].astype(dtype)), jnp.zeros((), jnp.float32)

    def loss(layers_, x):
        def body(x, layer):
            return stack.block(x, layer, config, qkv_part, out_part, attention_fn=attention)
        x, aux = jax.lax.scan(body, x, layers_)
        return x.astype(jnp.float32).sum() + aux.sum()

    width = h * d
    layers_ = {"q": jnp.zeros((layers, width, h, d)), "k": jnp.zeros((layers, width, kv_heads, d)),
               "v": jnp.zeros((layers, width, kv_heads, d)), "o": jnp.zeros((layers, h, d, width))}
    jaxpr = jax.make_jaxpr(jax.grad(loss))(layers_, jnp.zeros((b, s, width), dtype))
    forward = next(e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan")
    return [tuple(v.aval.shape) for v in forward.outvars[forward.params["num_carry"]:]]
