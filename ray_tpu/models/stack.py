"""The transformer block's skeleton, once for every model family (GPT, Llama,
OLMoE, ...), so neither parallelism semantics nor the names the benchmark
reads can drift between models.

A model supplies its two halves of a block as pure functions,
`qkv_part(x, layer, *streams) -> (q, k, v)` in (B, nh, S, hd) and
`out_part(x, o, layer, rng) -> (x, aux)`, and this module does the rest:
the remat decision (`config.remat`, `config.remat_policy`), the attention
dispatch between the halves, the scopes `blocks`, `layer_scan`, `qkv`,
`attention` and `head`, the per-layer dropout key, lax.scan over stacked
layer params and, when the mesh has pipeline > 1, the GPipe microbatch
schedule with optional in-region ring attention (parallel/pipeline.py); then
the head's product and the causal LM loss.

A model whose layers are not all of one kind hands `apply_stack` a `Pattern`:
the kinds by name, each with its parts (`qkv_part` None where the kind has no
attention in its middle and `out_part(x, None, layer, rng)` mixes the
positions itself; a third, the kind's own `attend`, where something other than
the attention dispatch stands between its two parts: a recurrent scan beside
kinds that attend), the kinds of the leading layers, of one period and of the
trailing layers, in the published order. Leading and trailing layers are
applied once each; the periods are one `lax.scan` whose body is a period's
layers unrolled, each place in the period with a stack of its own over the
periods. A stack of identical blocks is the pattern of one kind.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class Pattern(NamedTuple):
    """A stack whose layers differ in kind. Its parameters are `{"leading":
    [one tree a layer, ...], "period": [for each place in the period, the tree
    of the layers at that place, stacked over the periods on a leading axis],
    "trailing": [one tree a layer, ...]}`: the scan slices every place's
    stack by the period and nothing is indexed inside its body.
    `pattern_blocks` below writes that layout, for the parameters and for
    every tree laid out like them."""
    kinds: Dict[str, tuple]  # name -> (qkv_part | None, out_part[, the kind's own `block(attend=)`])
    period: Tuple[str, ...]  # the kinds of one period's layers
    n_periods: int
    leading: Tuple[str, ...] = ()  # the kinds of the layers before the first period
    trailing: Tuple[str, ...] = ()  # ... and after the last

    def layers(self, blocks) -> List[Tuple[str, Any]]:
        """(kind, the layer's own parameters) of every layer, in the published
        order: the stack as a plain list, for whoever walks it layer by layer."""
        out = list(zip(self.leading, blocks["leading"]))
        for p in range(self.n_periods):
            out += [(kind, jax.tree.map(lambda a: a[p], place))
                    for kind, place in zip(self.period, blocks["period"])]
        return out + list(zip(self.trailing, blocks["trailing"]))


# --------------------------------------------------------------------------- a stack's parameters
# A leaf of a layer's shape table `{name: (shape, init, logical axes)}`, nested as the layer's parameters are. `init`:
# what `draw` reads.
is_shape = lambda x: isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)  # noqa: E731


def pattern_blocks(leading, period, n_periods: int, trailing, layers: Callable):
    """A tree laid out as a `Pattern`'s parameters are, from the kinds as `Pattern` takes them:
    `layers(kind, i, ())` for layer i where it leads or trails, `layers(kind, i, (n_periods,))` for the stack of
    the layers (one a period) at the place of the period that layer i opens."""
    first_trailing = len(leading) + n_periods * len(period)
    return {
        # Lists: an empty tuple would read as a leaf of the logical axes' tree.
        "leading": [layers(kind, i, ()) for i, kind in enumerate(leading)],
        "period": [layers(kind, len(leading) + j, (n_periods,)) for j, kind in enumerate(period)],
        "trailing": [layers(kind, first_trailing + i, ()) for i, kind in enumerate(trailing)],
    }


def per_leaf(shapes, one: Callable, stack: Tuple[int, ...] = ()):
    """A tree like a layer's parameters: `one(name, shape, init, axes)` for every leaf of its shape table, in the
    order jax flattens them. With `stack` = (n,) the leaves are those of n such layers stacked: `shape` with n and
    `axes` with "layers" in front."""
    paths, tree = jax.tree.flatten_with_path(shapes, is_leaf=is_shape)
    lead = ("layers",) if stack else ()
    return jax.tree.unflatten(tree, [one(path[-1].key, stack + shape, init, lead + axes)
                                     for path, (shape, init, axes) in paths])


def lm_tree(config, layout, layer_shapes: Callable, leaf: Callable, layers: Optional[Callable] = None, *,
            embed=0.02, head: Optional[str] = None):
    """A tree like the parameters of a language model over a patterned stack, `leaf(name, shape, init, axes)` for
    every leaf: the embedding (`embed`, its init), the layers' (`layer_shapes(kind)`'s table through `per_leaf`, laid
    out by `pattern_blocks(*layout, ...)`), the final norm's scale and, where `head` names one, an untied head.
    `layers(kind, i, stack)`: the caller's own way to a layer in `per_leaf`'s place (an `init_params`: a key a layer)."""
    d = config.d_model
    layers = layers or (lambda kind, i, stack: per_leaf(layer_shapes(kind), leaf, stack))
    tree = {"embed": leaf("embed", (config.vocab_size, d), embed, ("vocab", "embed")),
            "blocks": pattern_blocks(*layout, layers),
            "final_norm": leaf("final_norm", (d,), "ones", (None,))}
    if head:
        tree[head] = leaf(head, (config.vocab_size, d), 0.02, ("vocab", "embed"))
    return tree


def draw(key, shape, init, dtype):
    """A leaf's first value. `init`: a normal's std; "ones" or "zeros" (a norm's scale, a bias: `key` is not read);
    or the name of a linear-attention gate's released initialisation, "A_log" and "dt_bias"."""
    if init in ("ones", "zeros"):
        return jnp.full(shape, float(init == "ones"), dtype)
    if init == "A_log":  # A ~ U(0, 16), kept off zero
        value = jnp.log(jax.random.uniform(key, shape, minval=1e-3, maxval=16.0))
    elif init == "dt_bias":  # the inverse softplus of dt ~ exp U(log 1e-3, log 1e-1)
        dt = jnp.exp(jax.random.uniform(key, shape, minval=math.log(1e-3), maxval=math.log(1e-1)))
        value = dt + jnp.log(-jnp.expm1(-dt))
    else:
        value = jax.random.normal(key, shape) * init
    return value.astype(dtype)


def draw_layer(key, shapes, stack: Tuple[int, ...], dtype):
    """A layer's parameters (with `stack` = (n,), n layers' stacked) from its shape table: `key`, the layer's own
    from its model's scheme, split a leaf."""
    keys = iter(jax.random.split(key, len(jax.tree.leaves(shapes, is_leaf=is_shape))))
    return per_leaf(shapes, lambda name, shape, init, axes: draw(next(keys), shape, init, dtype), stack)


def block(x, layer, config, qkv_part: Optional[Callable], out_part: Callable,
          attention_fn: Optional[Callable] = None, mesh=None, streams: tuple = (), rng=None,
          attend: Optional[Callable] = None):
    """One block on x (B, S, D): `out_part`'s (x, aux), under the remat the
    config asks for. `remat_policy` None recomputes everything in the block;
    "dots" saves matmul outputs across the remat boundary (less recompute,
    more memory); under "save_attn" the two parts are remat'ed each on its own
    while the attention call between them is not: its residuals (q/k/v/o and
    the kernel's lse) are saved, so the backward pass never re-runs the
    attention kernel, the most expensive op per byte saved. They are the very
    arrays `qkv_part` yields and `out_part` takes (`ops/flash_attention.py`
    keeps the caller's own), so the layer scan stacks each once: `out_part`'s
    checkpoint saves its `o` too, and a kernel that kept a copy under another
    shape would have the scan carry that value twice.

    A block with no attention in its middle (`qkv_part` None) is `out_part`
    alone, with `o` None: under any remat all of it is recomputed from its
    input ("save_attn" has nothing of it to save), "dots" keeping its matmul
    outputs.

    A layer whose attention is more than a function of q, k and v (a learned
    selection of keys, ...) brings `attend`: its `qkv_part` yields `(q, k, v,
    more)`, `attend(q, k, v, more, attention_fn, mesh)` stands where the
    dispatch stands and yields `(o, further)`, and `out_part(x, o, layer, rng,
    further)` takes what it yields beside `o` (a loss of its own to add to the
    aux sum, ...). Under "save_attn" it is not recomputed, like the call it
    replaces: what it keeps for its backward pass is saved.

    Scope names are read from the compiled program's `op_name`s by whoever
    splits a device trace by part of the step (PERF.md, "names")."""
    save_attn = config.remat and config.remat_policy == "save_attn" and qkv_part is not None
    if save_attn:
        qkv_part = jax.checkpoint(qkv_part, prevent_cse=False)
        out_part = jax.checkpoint(out_part, prevent_cse=False)

    def parts(x, layer, streams, rng):
        if qkv_part is None:
            return out_part(x, None, layer, rng)
        with jax.named_scope("qkv"):
            q, k, v, *more = qkv_part(x, layer, *streams)
        with jax.named_scope("attention"):
            if attend is None:
                o, further = resolve_attention(q, k, v, config.attention, attention_fn, mesh), ()  # (B, nh, S, hd)
            else:
                o, *further = attend(q, k, v, *more, attention_fn, mesh)
        return out_part(x, o, layer, rng, *further)

    if config.remat and not save_attn:
        dots = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        parts = jax.checkpoint(parts, prevent_cse=False,
                               policy=dots if config.remat_policy == "dots" else None)
    return parts(x, layer, streams, rng)


def apply_stack(
    blocks,  # stacked per-layer params, leading dim config.n_layer; a `Pattern`'s as it says
    x,  # (B, S, D)
    config,  # of any model: n_layer, remat, remat_policy, attention
    qkv_part: Optional[Callable] = None,
    out_part: Optional[Callable] = None,
    *,
    pattern: Optional[Pattern] = None,  # in place of the two parts, where layers differ in kind
    attention_fn: Optional[Callable],
    mesh=None,
    num_microbatches: Optional[int] = None,
    seq_streams: tuple = (),
    layers_rng=None,
    attend: Optional[Callable] = None,  # `block`'s: in place of the attention dispatch, in every layer whose kind brings none
    aux_per_layer: bool = False,  # every layer's aux as it is, laid out as a `Pattern`'s parameters are
) -> Tuple[Any, Any]:
    """Returns (activations, aux_sum): `block` over every layer, `out_part`'s
    scalar aux summed. With `aux_per_layer` nothing is summed: the second is
    `{"leading": [a layer's aux, ...], "period": [for each place in the period
    its layers' aux, stacked over the periods], "trailing": [...]}`, any tree a
    layer (a router's counts for a rule outside the loss, nothing for a layer
    that has none), for a model that reads its layers' statistics one by one.
    `seq_streams` are per-position arrays (leading dim S, e.g. RoPE cos/sin tables) handed to `qkv_part`; they shard with the
    sequence under context parallelism: inside the pipeline's manual region
    each rank receives its own slice, so global positions stay correct. With
    `layers_rng` (a model with dropout, training), `out_part` gets a key of
    its own for every layer, and under the pipeline for every microbatch."""
    uniform = pattern is None
    if uniform:
        pattern = Pattern({"layer": (qkv_part, out_part)}, ("layer",), config.n_layer)
        blocks = {"leading": [], "period": [blocks], "trailing": []}
    per_period = len(pattern.period)

    def one(kind, layer, index, attn, mb_idx, streams, x):
        rng = None
        if layers_rng is not None:
            rng = jax.random.fold_in(layers_rng, index)
            if mb_idx is not None:
                # Independent dropout mask per microbatch under PP.
                rng = jax.random.fold_in(rng, mb_idx)
        qkv, out, *own = pattern.kinds[kind]
        return block(x, layer, config, qkv, out, attn, mesh, streams, rng, own[0] if own else attend)

    def period_fn(first_layer, attn, mb_idx, streams, x, xs):
        """The scan's body over (a period's layers, idx), once the first four
        are bound: the period's layers in their order."""
        layers, idx = xs
        auxs = []
        for j, (kind, layer) in enumerate(zip(pattern.period, layers)):
            index = first_layer + (idx if per_period == 1 else idx * per_period + j)
            x, aux = one(kind, layer, index, attn, mb_idx, streams, x)
            auxs.append(aux)
        return x, tuple(auxs) if aux_per_layer else functools.reduce(jnp.add, auxs)

    n_pipeline = int(mesh.shape.get("pipeline", 1)) if mesh is not None else 1
    if n_pipeline > 1:
        assert not aux_per_layer, "the pipeline's schedule sums the layers' aux"
        if not uniform:
            raise NotImplementedError(
                "a stack whose layers differ in kind (stack.Pattern) cannot be cut into pipeline "
                f"stages yet (the mesh has pipeline={n_pipeline}): parallel/pipeline.py takes one "
                "stacked tree of identical layers")
        from ray_tpu.parallel.pipeline import pipeline_apply, to_stages

        # Combining PP with CP: the pipeline region is manual over `pipeline`,
        # so context parallelism joins the same region with the in-region ring
        # attention (a nested full shard_map can't reopen a mesh axis).
        n_context = int(mesh.shape.get("context", 1))
        context_manual = n_context > 1
        inner_attn = attention_fn
        if context_manual:
            from ray_tpu.parallel.ring_attention import ring_attention

            inner_attn = functools.partial(ring_attention, axis_name="context")

        def stack_fn(stage_local, xm, first_layer, mb_idx, streams):
            n_local = config.n_layer // n_pipeline
            xm, auxs = jax.lax.scan(
                functools.partial(period_fn, first_layer, inner_attn, mb_idx, streams),
                xm,
                ([stage_local], jnp.arange(n_local)),
            )
            return xm, jnp.sum(auxs)

        B = x.shape[0]
        M = num_microbatches or (2 * n_pipeline if B % (2 * n_pipeline) == 0 else n_pipeline)
        with jax.named_scope("blocks"):
            return pipeline_apply(
                mesh, to_stages(blocks["period"][0], n_pipeline), x, stack_fn, M,
                context_manual=context_manual,
                seq_streams=seq_streams,
            )
    with jax.named_scope("blocks"):
        n_leading, auxs = len(pattern.leading), []
        for i, (kind, layer) in enumerate(zip(pattern.leading, blocks["leading"])):
            x, aux = one(kind, layer, i, attention_fn, None, seq_streams, x)
            auxs.append(aux)
        # What the scan itself does round its body (the stacked residuals' buffers, the layers' slices)
        # gets a name of its own: a trace's account files it there, not under `blocks` bare.
        with jax.named_scope("layer_scan"):
            x, of_periods = jax.lax.scan(
                functools.partial(period_fn, n_leading, attention_fn, None, seq_streams),
                x,
                (blocks["period"], jnp.arange(pattern.n_periods)),
            )
        if not aux_per_layer:
            auxs.append(jnp.sum(of_periods))
        first_trailing = n_leading + pattern.n_periods * per_period
        for i, (kind, layer) in enumerate(zip(pattern.trailing, blocks["trailing"])):
            x, aux = one(kind, layer, first_trailing + i, attention_fn, None, seq_streams, x)
            auxs.append(aux)
        if aux_per_layer:
            return x, {"leading": auxs[:n_leading], "period": list(of_periods), "trailing": auxs[n_leading:]}
        return x, functools.reduce(jnp.add, auxs)


def resolve_attention(q, k, v, attention_mode: str, attention_fn: Optional[Callable],
                      mesh=None, sm_scale: Optional[float] = None):
    """One attention-backend dispatch for every model family: caller-injected
    fn (ring/Ulysses wrappers) wins; "xla" forces the plain-XLA form; "auto"
    and "flash" take `flash_attention`'s own choice for this platform and
    shape (`ops.flash_attention.select_backend` says which), with the kernel
    partitioned over `mesh`. `sm_scale`: a model's own scale of the scores in
    place of `head_dim^-1/2` (a rotation scaled to a longer context, from a
    kind's own `attend`); a caller's fn takes none."""
    if attention_fn is not None:
        if sm_scale is not None:
            raise NotImplementedError("a caller's attention function (ring, Ulysses) takes no softmax scale of the model's")
        return attention_fn(q, k, v)
    from ray_tpu.ops.flash_attention import flash_attention, xla_attention

    if attention_mode == "xla":
        return xla_attention(q, k, v, causal=True, sm_scale=sm_scale)
    if mesh is not None and int(mesh.shape.get("pipeline", 1)) > 1:
        # Inside the pipeline's manual region a second shard_map cannot
        # reopen the mesh; the kernel runs unpartitioned there.
        mesh = None
    return flash_attention(q, k, v, causal=True, sm_scale=sm_scale, mesh=mesh)


def lm_head(x, norm: Callable, table, dtype):
    """Logits (B, S, V) in float32 of the final activations x (B, S, D) against
    `table` (V, D), the tied embedding or a head of its own, after the model's
    final `norm`: bf16 operands on the MXU, f32 accumulation. An f32 x f32
    matmul here would run at a fraction of MXU rate, and this matmul is ~30%
    of GPT-2-small's FLOPs."""
    with jax.named_scope("head"):
        return jnp.einsum(
            "bsd,vd->bsv",
            norm(x).astype(dtype),
            table.astype(dtype),
            preferred_element_type=jnp.float32,
        )


def cross_entropy(logits, targets):
    """Each position's cross entropy (B, S) f32, fused: logsumexp - logit[target], one reduction over V
    instead of materializing the (B, S, V) log-softmax (saves ~2x V-sized HBM traffic). The target's logit
    is picked by a compare with the vocabulary's iota and a sum, and by no gather: a gather's gradient is a
    scatter into zeros of the logits' shape, which costs a step of one row a device four logits-sized arrays
    (PR 68), and this one's is a `where` that XLA fuses into the head's backward products. A target outside
    [0, V) hits no column and reads a logit of 0, so the entropy is `lse` and the logits' gradient the
    softmax alone (the gather counted a negative one from the end and read NaN past either end; no mix of the
    benchmark draws one)."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    hit = jax.lax.broadcasted_iota(targets.dtype, logits.shape, logits.ndim - 1) == targets[..., None]
    return lse - jnp.sum(jnp.where(hit, logits, 0), axis=-1)


def causal_lm_loss(logits, targets, mask=None, weights=None):
    """The mean of `cross_entropy` over every position, or with `mask` (broadcast
    against (B, S)) over the positions it keeps: weights of 0 and 1,
    normalised by their sum. `weights` (float, broadcast against (B, S)) are
    an objective's own, and their sum is no normaliser: the weighted sum over
    the number of positions (block diffusion's 1 / t at the masked positions
    of a row of S, `sdar.py`)."""
    with jax.named_scope("loss"):
        ce = cross_entropy(logits, targets)
        if weights is not None:
            assert mask is None, "weights of their own, or a mask's 0 and 1"
            return (jnp.broadcast_to(weights, targets.shape) * ce).sum() / targets.size
        if mask is None:
            return ce.mean()
        mask = jnp.broadcast_to(mask, targets.shape)
        return jnp.where(mask, ce, 0.0).sum() / mask.sum()


def lm_loss(forward: Callable, params, batch, config, attention_fn=None, step_rng=None,
            mesh=None, num_microbatches=None):
    """Causal LM cross entropy (mean over tokens) of a model's `forward` on
    `batch`, {"tokens": (B, S+1)} or {"inputs", "targets"}, plus the auxiliary
    loss `forward(..., return_aux=True)` returns beside the logits: a scalar
    the model has already weighted, or None where it has none. A model with
    further prediction depths (`config.n_predict_layers`: each position also
    predicts tokens beyond its next) computes their loss as that scalar, and
    its `forward` is handed the `targets` for it. `step_rng` is the step's key
    (`make_train_step` folds one from `state.step` for every model): dropout's
    in a model that has some, unused in the others."""
    if "inputs" in batch:
        inputs, targets = batch["inputs"], batch["targets"]
    else:
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
    deeper = {"targets": targets} if getattr(config, "n_predict_layers", 0) else {}
    logits, aux = forward(
        params, inputs, config, attention_fn, step_rng, mesh, num_microbatches,
        return_aux=True, **deeper,
    )
    loss = causal_lm_loss(logits, targets)
    return loss if aux is None else loss + aux
