"""`TrainState.compute` (PR 64): the grouped-matmul kernels' matrices in the compute dtype, held beside their float32
masters and written by the optimizer's pass. Against the parent's step (the cast inside the loss, no copy), on the
nano configurations of four expert families: the same loss, gradient norm, parameters and moments, bit for bit, three
steps running; every copy its master's `astype` after each; no leaf of `params` or `opt_state` in another dtype. A
dense model's copy is empty, a state that arrives without one steps and comes back with one, and on a mesh a copy is
sharded as its master.

Bit for bit needs two things said to XLA's CPU compiler (`STRICT`). On the chip the kernels write a matrix's gradient
in bf16 themselves (`grouped_matmul._bwd_rule`), so the parent's widening and this tree's are the same convert. Off it
the XLA form runs, and with `xla_allow_excess_precision` (the default) the compiler drops the parent's f32 -> bf16 ->
f32 pair and keeps that gradient unrounded. And inside one fused loop LLVM contracts AdamW's `0.9 mu + 0.1 g` into a
multiply-add around either product, by the order it meets them in (a last bit of mu in a third of its elements, in two
nanos of four): with no fusion pass every operation is a loop of its own and rounds as the program says."""

import glob
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from aot_v5e import configuration
from ray_tpu.models import create_train_state, default_optimizer, make_train_step, shard_batch
from ray_tpu.models.training import TrainState, compute_copy, leaves_by_path, model_for

# (a frozen buffer: LFM2's `expert_bias`; `update_buffers`: Trinity's)
EXPERT_NANOS = ("olmoe-nano", "solar-open2-nano", "lfm2-nano", "trinity-nano")
STEPS = 3
STRICT = {"xla_allow_excess_precision": False, "xla_disable_hlo_passes": "fusion"}


def batches(c, n=STEPS):
    rng = np.random.default_rng(64)
    shape = (c["batch"]["global_rows"], c["batch"]["seq"] + 1)
    return [{"tokens": jnp.asarray(rng.integers(0, c["vocab_size"], shape), jnp.int32)} for _ in range(n)]


def strict(step):
    """`step` compiled so that every operation rounds where the program says so (`STRICT`)."""
    compiled = {}

    def call(*args):
        key = jax.tree.structure(args)
        if key not in compiled:
            compiled[key] = step.lower(*args).compile(compiler_options=STRICT)
        return compiled[key](*args)

    return call


def parents_step(config, optimizer):
    """`make_train_step` as the parent of PR 64 wrote it (its body, word for word): the loss differentiated at the
    float32 parameters, so every `astype` of a kernel's operand stands inside it, forward and backward."""
    base_rng = jax.random.PRNGKey(0x5eed)
    frozen = getattr(model_for(config), "frozen_params", None)
    update_buffers = getattr(model_for(config), "update_buffers", None)

    def step_fn(state: TrainState, batch):
        step_rng = jax.random.fold_in(base_rng, state.step)

        def loss_of(p):
            return model_for(config).loss_fn(p, batch, config, None, step_rng, mesh=None)

        if update_buffers is None:
            loss, grads = jax.value_and_grad(loss_of)(state.params)
        else:
            (loss, stats), grads = jax.value_and_grad(loss_of, has_aux=True)(state.params)
        with jax.named_scope("optimizer"):
            updates, new_opt = optimizer.update(grads, state.opt_state, state.params)
            if frozen is not None:
                updates = jax.tree.map(lambda u, is_buffer: jnp.zeros_like(u) if is_buffer else u,
                                       updates, frozen(config))
            new_params = optax.apply_updates(state.params, updates)
        if update_buffers is not None:
            with jax.named_scope("buffers"):
                new_params = update_buffers(new_params, stats, config)
        new_state = TrainState(params=new_params, opt_state=new_opt, step=state.step + 1)
        with jax.named_scope("grad_norm"):
            gnorm = optax.global_norm(grads)
        return new_state, {"loss": loss, "grad_norm": gnorm, "step": new_state.step}

    return jax.jit(step_fn)


@pytest.fixture(scope="module", params=EXPERT_NANOS)
def both(request):
    """A nano's three steps by this tree's step and by the parent's, from one state: [(mine, theirs)] a step, each
    (state, metrics) on the host."""
    c, cfg = configuration(request.param)
    optimizer = default_optimizer(learning_rate=1e-2)  # large enough to move a bf16 copy every step
    mine = create_train_state(cfg, jax.random.PRNGKey(3), optimizer)
    theirs = TrainState(params=mine.params, opt_state=mine.opt_state, step=mine.step)
    step, parents = strict(make_train_step(cfg, optimizer, donate=False)), strict(parents_step(cfg, optimizer))
    walked = []
    for batch in batches(c):
        (mine, m), (theirs, t) = step(mine, batch), parents(theirs, batch)
        walked.append(jax.device_get(((mine, m), (theirs, t))))
    return cfg, walked


def test_an_expert_models_copy_is_its_layers_three_matrices_and_nothing_else(both):
    cfg, walked = both
    (state, _), _ = walked[0]
    names = [path.rsplit("[", 1)[1] for path in state.compute]
    assert set(names) == {"'w_down']", "'w_gate']", "'w_up']"} and len(names) % 3 == 0
    axes = leaves_by_path(model_for(cfg).param_logical_axes(cfg), lambda x: isinstance(x, tuple))
    assert set(state.compute) == {path for path, names in axes.items() if "expert" in names}
    assert all(x.dtype == cfg.dtype for x in state.compute.values())


@pytest.mark.parametrize("what", ["loss", "grad_norm"])
def test_the_steps_numbers_are_the_parents_bit_for_bit(both, what):
    _, walked = both
    for (_, mine), (_, theirs) in walked:
        assert np.isfinite(mine[what]) and mine[what].tobytes() == theirs[what].tobytes(), (mine, theirs)


@pytest.mark.parametrize("tree", ["params", "opt_state"])
def test_every_leaf_is_the_parents_bit_for_bit_and_none_changed_its_dtype(both, tree):
    cfg, walked = both
    before = None
    for (mine, _), (theirs, _) in walked:
        ours, parents = leaves_by_path(getattr(mine, tree)), leaves_by_path(getattr(theirs, tree))
        assert ours.keys() == parents.keys()
        for path in ours:
            assert ours[path].dtype == parents[path].dtype and ours[path].shape == parents[path].shape, path
            assert ours[path].tobytes() == parents[path].tobytes(), path
        assert {str(x.dtype) for x in ours.values() if x.ndim} == {str(np.dtype(cfg.param_dtype))}
        if tree == "params" and before is not None:  # the masters the copies stand for did move
            assert all(np.any(ours[path] != before[path]) for path in mine.compute)
        before = ours


def test_after_each_step_every_copy_is_its_masters_astype(both):
    cfg, walked = both
    for (mine, _), (theirs, _) in walked:
        assert theirs.compute == {}
        masters = leaves_by_path(mine.params)
        for path, copy in mine.compute.items():
            want = np.asarray(jnp.asarray(masters[path]).astype(cfg.dtype))
            assert copy.dtype == want.dtype and copy.tobytes() == want.tobytes(), path


def test_a_dense_models_copy_is_empty_and_its_step_lowers_to_the_parents_text():
    c, cfg = configuration("gpt2-nano")
    optimizer = default_optimizer(1e-3)
    state = jax.eval_shape(lambda: create_train_state(cfg, jax.random.PRNGKey(0), optimizer))
    assert state.compute == {} and compute_copy(cfg, state.params) == {}
    batch = {"tokens": jax.ShapeDtypeStruct((c["batch"]["global_rows"], c["batch"]["seq"] + 1), jnp.int32)}
    mine = make_train_step(cfg, optimizer, donate=False).lower(state, batch).as_text()
    assert mine == parents_step(cfg, optimizer).lower(state, batch).as_text()


def test_a_state_built_without_a_copy_steps_as_the_parent_and_comes_back_with_one():
    c, cfg = configuration("olmoe-nano")
    optimizer = default_optimizer(1e-2)
    whole = create_train_state(cfg, jax.random.PRNGKey(3), optimizer)
    bare = TrainState(params=whole.params, opt_state=whole.opt_state, step=whole.step)
    assert bare.compute == {}
    step, (batch, again) = strict(make_train_step(cfg, optimizer, donate=False)), batches(c, 2)
    (after_bare, m_bare), (after_whole, m_whole) = step(bare, batch), step(whole, batch)
    assert after_bare.compute.keys() == whole.compute.keys()
    same = jax.tree.map(lambda a, b: np.asarray(a).tobytes() == np.asarray(b).tobytes(),
                        (after_bare, m_bare), (after_whole, m_whole))
    assert all(jax.tree.leaves(same))
    assert np.isfinite(float(step(after_bare, again)[1]["loss"]))


def test_on_a_mesh_a_copy_is_sharded_as_its_master():
    from ray_tpu.parallel import MeshSpec

    c, cfg = configuration("olmoe-nano")
    optimizer = default_optimizer(1e-2)
    mesh = MeshSpec(fsdp=4).build(jax.devices()[:4])
    state = create_train_state(cfg, jax.random.PRNGKey(3), optimizer, mesh=mesh)
    masters = leaves_by_path(state.params)
    assert len(state.compute) == 3
    for path, copy in state.compute.items():
        assert copy.sharding == masters[path].sharding and not copy.sharding.is_fully_replicated, path
    rows = {"tokens": jnp.tile(batches(c, 1)[0]["tokens"], (2, 1))}  # four rows: one a device
    after, metrics = make_train_step(cfg, optimizer, mesh=mesh)(state, shard_batch(rows, mesh))
    assert np.isfinite(float(metrics["loss"]))
    for path, copy in after.compute.items():
        assert copy.sharding == leaves_by_path(after.params)[path].sharding, path


def test_create_train_state_says_what_engaged_on_the_train_paths_tracing(tmp_path):
    """`annotate("ray_tpu.train.create_state", compute_copy_bytes=..., leaves=...)`: in a profiler session, the copy's
    bytes and leaves for an expert model and zeros for a dense one."""
    from jax.profiler import ProfileData

    optimizer = default_optimizer(1e-3)
    jax.profiler.start_trace(str(tmp_path))
    try:
        states = {name: create_train_state(configuration(name)[1], jax.random.PRNGKey(0), optimizer)
                  for name in ("olmoe-nano", "gpt2-nano")}
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    said = [dict(ev.stats) for plane in ProfileData.from_file(path).planes if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events if ev.name == "ray_tpu.train.create_state"]
    copy_bytes = sum(x.nbytes for x in states["olmoe-nano"].compute.values())
    assert copy_bytes > 0 and said == [{"compute_copy_bytes": copy_bytes, "leaves": 3},
                                       {"compute_copy_bytes": 0, "leaves": 0}]
