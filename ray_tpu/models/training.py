"""Sharded train-state + train-step factory for the model zoo.

The SPMD recipe (scaling-book style, SURVEY.md §7): params are initialized
*under jit with explicit out_shardings* (so big models never materialize
unsharded), the optimizer state inherits param shardings through propagation,
and the train step is a single jitted function with donated state — XLA inserts
the DP gradient all-reduce / FSDP all-gathers / TP collectives from the sharding
annotations alone. Loss-parity note: this is the exact computation a bare-JAX
script would run; the framework adds no per-step Python between device
dispatches (the reference's "Ray adds ~0% overhead over DDP" property).

The compute copy. Parameters are `param_dtype` (float32), products are
`dtype` (bf16). XLA's own matmuls convert in their operand read; a Pallas call
takes a materialised operand, so the grouped-matmul kernels' matrices were
walked in float32 and written in bf16 once forward and once backward, every
step, for weights that change once a step. `TrainState.compute` holds those
leaves in `dtype` beside the float32 master: the loss is differentiated at a
view of the parameters in which they stand (`w.astype(dtype)` of a bf16 array
is the array), their gradient is widened to the master's dtype before the
optimizer sees it (what the transpose of `astype` did), and the optimizer's
pass writes the next copy from the value it has just made. `params` and
`opt_state` stay `param_dtype` in every leaf: the master, the moments and the
update are the values they were, and the products' operand is too. The copy is
derived: whatever saves a state may drop it (`compute={}`), and the first step
serves such a state as it always was served and hands back one that has it.
"""

from __future__ import annotations

import dataclasses
import functools
import sys
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.parallel import ShardingRules, batch_spec
from ray_tpu.util.tracing import annotate


def model_for(config):
    """A configuration's model: the module that defines its class (gpt, llama,
    olmoe, resnet, ... or a user's own) and, beside it, what one
    TrainState/step factory needs of any model. A model that keeps buffers
    among its parameters (a router's selection bias, ...) also defines
    `frozen_params(config)`, a tree of bools like the parameters', True where
    no optimizer step may change the leaf (`make_train_step`). One whose
    source states a rule that moves such a buffer outside the loss defines
    `update_buffers(params, stats, config)` beside it: its `loss_fn` then
    returns `(loss, stats)`, and the step applies the rule to the updated
    parameters after the optimizer (`trinity.py`: the routers' selection bias,
    from the step's own counts)."""
    module = sys.modules[type(config).__module__]
    missing = [name for name in ("init_params", "param_logical_axes", "loss_fn")
               if not callable(getattr(module, name, None))]
    if missing:
        raise TypeError(
            f"{type(config).__qualname__} is no model's configuration: "
            f"its module {module.__name__} defines no {', '.join(missing)}")
    return module


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jax.Array
    # {key path of a parameter leaf: that leaf in `config.dtype`} (`compute_copy`); empty for a model
    # that feeds no kernel's grouped operand, and for a state that arrives without one.
    compute: Any = dataclasses.field(default_factory=dict)


def _is_axes(x) -> bool:
    return isinstance(x, tuple) and all(a is None or isinstance(a, str) for a in x)


def leaves_by_path(tree, is_leaf=None) -> Dict[str, Any]:
    """{`keystr` of a leaf's path: the leaf}: how `TrainState.compute` names a parameter."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_leaf)
    return {jax.tree_util.keystr(path): leaf for path, leaf in leaves}


def compute_copy(config, params) -> Dict[str, Any]:
    """{key path: leaf.astype(config.dtype)} of the parameters that are a kernel's grouped operand: a leaf
    whose logical axes name `expert` (the routed experts' three matrices; not the router, not a shared
    expert, whose `swiglu` is XLA's and converts in its operand read) and that is not `config.dtype` already.
    Read from the tree: a model with no such leaf has an empty copy."""
    axes = leaves_by_path(model_for(config).param_logical_axes(config), _is_axes)
    return {path: p.astype(config.dtype) for path, p in leaves_by_path(params).items()
            if "expert" in axes[path] and p.dtype != config.dtype}


def _with_copies(params, compute):
    """The parameters as the loss reads them: a leaf that has a compute copy is the copy."""
    return jax.tree_util.tree_map_with_path(lambda path, p: compute.get(jax.tree_util.keystr(path), p), params)


def param_shardings(config, mesh, rules: ShardingRules):
    model = model_for(config)
    axes = model.param_logical_axes(config)
    shapes = jax.eval_shape(lambda: model.init_params(config, jax.random.PRNGKey(0)))
    return jax.tree.map(
        lambda ax, s: rules.sharding(mesh, ax, shape=s.shape),
        axes,
        shapes,
        is_leaf=_is_axes,
    )


def create_train_state(
    config,
    key,
    optimizer,
    mesh=None,
    rules: Optional[ShardingRules] = None,
) -> TrainState:
    """Initialize params and optimizer state, both sharded, under jit, and the
    compute copy (`compute_copy`), each leaf sharded as its master."""
    init_params = lambda k: model_for(config).init_params(config, k)  # noqa: E731
    copy = functools.partial(compute_copy, config)
    copied = jax.eval_shape(lambda k: copy(init_params(k)), key)
    copy_bytes = sum(x.size * x.dtype.itemsize for x in copied.values())
    with annotate("ray_tpu.train.create_state", compute_copy_bytes=copy_bytes, leaves=len(copied)):
        if mesh is None:
            params = jax.jit(init_params)(key)
            opt_state = jax.jit(optimizer.init)(params)
            copy_shardings = None
        else:
            shardings = param_shardings(config, mesh, rules or ShardingRules())
            params = jax.jit(init_params, out_shardings=shardings)(key)
            # Whatever in the optimizer's state is laid out like the parameters
            # (AdamW's mu and nu) is sharded like them, the rest (counts) is whole
            # everywhere. Propagation alone leaves the moments whole on every
            # device: 12.4 GB a chip for gpt2-xl, for as long as it takes to lay
            # them out again (PERF.md section 7, the cold run's margin).
            structure = jax.tree.structure(params)
            like_params = lambda x: jax.tree.structure(x) == structure  # noqa: E731
            whole = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
            opt_shardings = jax.tree.map(
                lambda x: shardings if like_params(x) else whole,
                jax.eval_shape(optimizer.init, params), is_leaf=like_params)
            opt_state = jax.jit(optimizer.init, out_shardings=opt_shardings)(params)
            copy_shardings = {path: s for path, s in leaves_by_path(shardings).items() if path in copied}
        compute = jax.jit(copy, out_shardings=copy_shardings)(params) if copied else {}
    return TrainState(params=params, opt_state=opt_state, step=jnp.zeros((), jnp.int32), compute=compute)


def make_train_step(
    config,
    optimizer,
    mesh=None,
    attention_fn: Optional[Callable] = None,
    donate: bool = True,
) -> Callable[[TrainState, Dict[str, Any]], Tuple[TrainState, Dict[str, Any]]]:
    """One fused SPMD update: loss -> grads -> optimizer -> new state."""

    base_rng = jax.random.PRNGKey(0x5eed)
    frozen = getattr(model_for(config), "frozen_params", None)
    update_buffers = getattr(model_for(config), "update_buffers", None)

    def step_fn(state: TrainState, batch):
        # The step's key, for whatever the model draws anew each step (dropout's
        # masks, a diffusion objective's noise); a model that draws nothing
        # ignores it, and the fold is dead code in its program.
        step_rng = jax.random.fold_in(base_rng, state.step)

        def loss_of(p):
            return model_for(config).loss_fn(
                p, batch, config, attention_fn, step_rng, mesh=mesh
            )

        import optax

        # Differentiated where a kernel's operand is its compute copy: nothing is left to convert in
        # the loss, forward or backward, and the copy's gradient comes back in the copy's dtype.
        seen = _with_copies(state.params, state.compute)
        if update_buffers is None:
            loss, grads = jax.value_and_grad(loss_of)(seen)
        else:  # the loss's statistics come out of the gradient pass beside it
            (loss, stats), grads = jax.value_and_grad(loss_of, has_aux=True)(seen)
        with jax.named_scope("optimizer"):
            # ... and is widened to its master's, as the transpose of `astype` widened it.
            grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads, state.params)
            updates, new_opt = optimizer.update(grads, state.opt_state, state.params)
            if frozen is not None:  # a buffer takes no update, a decoupled weight decay's neither
                updates = jax.tree.map(lambda u, is_buffer: jnp.zeros_like(u) if is_buffer else u,
                                       updates, frozen(config))
            new_params = optax.apply_updates(state.params, updates)
        if update_buffers is not None:  # neither a gradient's nor the optimizer's: the model's own rule
            with jax.named_scope("buffers"):
                new_params = update_buffers(new_params, stats, config)
        with jax.named_scope("optimizer"):  # one more output of the pass that holds the new value
            new_compute = compute_copy(config, new_params)
        new_state = TrainState(
            params=new_params, opt_state=new_opt, step=state.step + 1, compute=new_compute
        )
        with jax.named_scope("grad_norm"):
            gnorm = optax.global_norm(grads)
        return new_state, {"loss": loss, "grad_norm": gnorm, "step": new_state.step}

    return jax.jit(step_fn, donate_argnums=(0,) if donate else ())


def shard_batch(batch: Dict[str, Any], mesh):
    """Place a host batch onto the mesh with the canonical batch sharding
    (batch dim over (data, fsdp), sequence over context — `parallel.batch_spec`)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def put(x):
        if x.ndim == 2:
            spec = batch_spec()  # (batch over data/fsdp, sequence over context)
        else:
            # 1-D labels and N-D image tensors: only the batch dim shards
            # (context parallelism is a sequence-axis concept; image H/W must
            # not land on it).
            spec = P(("data", "fsdp"))
        return jax.device_put(x, NamedSharding(mesh, spec))

    nbytes = sum(getattr(x, "nbytes", 0) for x in jax.tree.leaves(batch))
    with annotate("ray_tpu.train.shard_batch", bytes=nbytes):
        return jax.tree.map(put, batch)


def default_optimizer(
    learning_rate: float = 3e-4,
    weight_decay: float = 0.1,
    b1: float = 0.9,
    b2: float = 0.95,
    grad_clip: float = 1.0,
    warmup_steps: int = 0,
    total_steps: int = 0,
):
    """AdamW with cosine schedule + global-norm clipping (GPT-2 recipe)."""
    import optax

    if total_steps:
        lr = optax.warmup_cosine_decay_schedule(
            0.0, learning_rate, max(warmup_steps, 1), total_steps
        )
    else:
        lr = learning_rate
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(lr, b1=b1, b2=b2, weight_decay=weight_decay),
    )
