"""Model + sharded training tests on the virtual 8-device mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (
    GPTConfig,
    create_train_state,
    default_optimizer,
    forward,
    init_params,
    make_train_step,
    num_params,
    shard_batch,
)
from ray_tpu.parallel import MeshSpec, ShardingRules


@pytest.fixture(scope="module")
def nano():
    return GPTConfig.nano(dtype=jnp.float32)


@pytest.fixture(scope="module")
def on_one_device(nano):
    """The losses of nano's step over some batches on a one-device mesh, from `PRNGKey(1)`'s state at learning
    rate 1e-3: the yardstick of the two parity tests, its step compiled once for both."""
    opt = default_optimizer(learning_rate=1e-3)
    mesh1 = MeshSpec(data=1).build(jax.devices()[:1])
    first = create_train_state(nano, jax.random.PRNGKey(1), opt, mesh=mesh1)
    step1 = make_train_step(nano, opt, mesh=mesh1)

    def losses(batches):
        state, out = jax.tree.map(jnp.copy, first), []  # the step donates its state
        for b in batches:
            state, m = step1(state, shard_batch(b, mesh1))
            out.append(float(m["loss"]))
        return out

    return losses


def _batch(rng, batch=8, seq=64, vocab=256):
    start = rng.integers(0, vocab - 56, size=(batch, 1))
    toks = (start + np.arange(seq + 1)) % vocab
    return {"tokens": toks.astype(np.int32)}


def test_forward_shapes(nano):
    params = init_params(nano, jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = forward(params, tokens, nano)
    assert logits.shape == (2, 16, nano.vocab_size)
    assert logits.dtype == jnp.float32


def test_num_params_matches_tree(nano):
    params = init_params(nano, jax.random.PRNGKey(0))
    actual = sum(p.size for p in jax.tree.leaves(params))
    assert actual == num_params(nano)


def test_training_reduces_loss_dp_tp(nano):
    mesh = MeshSpec(data=2, tensor=4).build()
    opt = default_optimizer(learning_rate=1e-2)
    state = create_train_state(nano, jax.random.PRNGKey(0), opt, mesh=mesh)
    step = make_train_step(nano, opt, mesh=mesh)
    rng = np.random.default_rng(0)
    first = None
    for i in range(25):
        state, metrics = step(state, shard_batch(_batch(rng), mesh))
        if first is None:
            first = float(metrics["loss"])
    last = float(metrics["loss"])
    assert last < first * 0.5, (first, last)


def test_fsdp_mesh_shards_params(nano):
    mesh = MeshSpec(fsdp=8).build()
    opt = default_optimizer()
    state = create_train_state(nano, jax.random.PRNGKey(0), opt, mesh=mesh)
    # embed-dim leaves shard over fsdp (d_model=64 divisible by 8)
    spec = state.params["blocks"]["fc_w"].sharding.spec
    assert "fsdp" in str(spec)


def test_fsdp_mesh_shards_the_optimizer_moments_like_the_params(nano):
    """AdamW's mu and nu are created sharded like the parameters, never whole
    on every device (propagation alone left them whole: 12.4 GB a chip for
    gpt2-xl), and the step hands them back laid out the same way."""
    mesh = MeshSpec(fsdp=8).build()
    opt = default_optimizer()
    state = create_train_state(nano, jax.random.PRNGKey(0), opt, mesh=mesh)
    structure = jax.tree.structure(state.params)
    moments = [t for t in jax.tree.leaves(state.opt_state,
                                          is_leaf=lambda x: jax.tree.structure(x) == structure)
               if jax.tree.structure(t) == structure]
    assert len(moments) == 2  # mu, nu
    for tree in moments:
        for p, m in zip(jax.tree.leaves(state.params), jax.tree.leaves(tree)):
            assert m.sharding.is_equivalent_to(p.sharding, p.ndim), (p.shape, m.sharding)
            assert m.addressable_shards[0].data.shape == p.addressable_shards[0].data.shape
    state2, _ = make_train_step(nano, opt, mesh=mesh)(
        state, shard_batch(_batch(np.random.default_rng(0)), mesh))
    mu = jax.tree.leaves(state2.opt_state, is_leaf=lambda x: jax.tree.structure(x) == structure)
    mu = next(t for t in mu if jax.tree.structure(t) == structure)
    assert "fsdp" in str(mu["blocks"]["qkv_w"].sharding.spec)


def test_dp_equals_single_device_loss(nano, on_one_device):
    """DP loss-curve parity: same data, same init -> same loss whether the mesh
    is 1 device or 8 (the reference's torch-parity property, SURVEY.md §6)."""
    opt = default_optimizer(learning_rate=1e-3)
    rng = np.random.default_rng(42)
    batches = [_batch(rng) for _ in range(3)]

    mesh8 = MeshSpec(data=8).build()
    s8 = create_train_state(nano, jax.random.PRNGKey(1), opt, mesh=mesh8)
    step8 = make_train_step(nano, opt, mesh=mesh8)
    losses8 = []
    for b in batches:
        s8, m = step8(s8, shard_batch(b, mesh8))
        losses8.append(float(m["loss"]))

    losses1 = on_one_device(batches)

    np.testing.assert_allclose(losses8, losses1, rtol=1e-4)


def test_ring_attention_matches_full():
    from ray_tpu.ops.flash_attention import xla_attention
    from ray_tpu.parallel.ring_attention import ring_attention_sharded

    mesh = MeshSpec(context=8).build()
    key = jax.random.PRNGKey(0)
    b, h, s, d = 2, 2, 128, 32
    q, k, v = (jax.random.normal(kk, (b, h, s, d), jnp.float32) for kk in jax.random.split(key, 3))
    ref = xla_attention(q, k, v, causal=True)
    out = ring_attention_sharded(mesh, q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ulysses_attention_matches_full():
    import functools

    from jax.sharding import PartitionSpec as P

    from ray_tpu.ops.flash_attention import xla_attention
    from ray_tpu.parallel.ring_attention import ulysses_attention

    mesh = MeshSpec(context=2).build(jax.devices()[:2])
    key = jax.random.PRNGKey(1)
    b, h, s, d = 2, 4, 64, 16
    q, k, v = (jax.random.normal(kk, (b, h, s, d), jnp.float32) for kk in jax.random.split(key, 3))
    spec = P(None, None, "context", None)
    fn = jax.shard_map(
        functools.partial(ulysses_attention, axis_name="context", axis_size=2),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False,
    )
    ref = xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(fn(q, k, v)), np.asarray(ref), atol=2e-5)


def test_context_parallel_training(nano):
    """Train with sequence sharded over the context axis (ring attention)."""
    import functools

    from ray_tpu.parallel.ring_attention import ring_attention_sharded

    mesh = MeshSpec(data=2, context=4).build()
    attention_fn = functools.partial(ring_attention_sharded, mesh)
    opt = default_optimizer(learning_rate=1e-2)
    state = create_train_state(nano, jax.random.PRNGKey(0), opt, mesh=mesh)
    step = make_train_step(nano, opt, mesh=mesh, attention_fn=attention_fn)
    rng = np.random.default_rng(0)
    first = None
    for _ in range(15):
        toks = _batch(rng)["tokens"]
        # With the sequence sharded over context, feed pre-split inputs/targets
        # whose seq length divides the context axis.
        batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:]}
        state, metrics = step(state, shard_batch(batch, mesh))
        if first is None:
            first = float(metrics["loss"])
    assert float(metrics["loss"]) < first


def test_pipeline_parallel_equals_single_device_loss(nano, on_one_device):
    """PP loss parity: a data=2 x pipeline=2 x tensor=2 mesh (GPipe microbatch
    schedule, parallel/pipeline.py) trains identically to a 1-device mesh."""
    opt = default_optimizer(learning_rate=1e-3)
    rng = np.random.default_rng(7)
    batches = [_batch(rng) for _ in range(3)]

    meshp = MeshSpec(data=2, pipeline=2, tensor=2).build()
    sp = create_train_state(nano, jax.random.PRNGKey(1), opt, mesh=meshp)
    # Each stage group stores only n_layer/pipeline layers.
    assert "pipeline" in str(sp.params["blocks"]["fc_w"].sharding.spec)
    stepp = make_train_step(nano, opt, mesh=meshp)
    lossesp = []
    for b in batches:
        sp, m = stepp(sp, shard_batch(b, meshp))
        lossesp.append(float(m["loss"]))

    losses1 = on_one_device(batches)

    np.testing.assert_allclose(lossesp, losses1, rtol=1e-4)


def test_pipeline_with_context_parallel(nano):
    """PP x CP: ring attention joins the pipeline's manual region."""
    mesh = MeshSpec(pipeline=2, context=2, tensor=2).build()
    opt = default_optimizer(learning_rate=1e-2)
    state = create_train_state(nano, jax.random.PRNGKey(0), opt, mesh=mesh)
    step = make_train_step(nano, opt, mesh=mesh)
    rng = np.random.default_rng(0)
    first = None
    for _ in range(10):
        toks = _batch(rng)["tokens"]
        batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:]}  # seq 64 % cp=2
        state, metrics = step(state, shard_batch(batch, mesh))
        if first is None:
            first = float(metrics["loss"])
    assert float(metrics["loss"]) < first, (first, float(metrics["loss"]))


def test_moe_expert_parallel_training(nano):
    """Switch-MoE MLP with experts sharded over the expert axis: loss falls,
    expert weights actually shard (models/moe.py, EP via token all-to-all)."""
    cfg = GPTConfig.nano(dtype=jnp.float32, moe_experts=4)
    mesh = MeshSpec(data=2, expert=4).build()
    opt = default_optimizer(learning_rate=1e-2)
    state = create_train_state(cfg, jax.random.PRNGKey(0), opt, mesh=mesh)
    assert "expert" in str(state.params["blocks"]["moe"]["w_gate"].sharding.spec)
    step = make_train_step(cfg, opt, mesh=mesh)
    rng = np.random.default_rng(0)
    first = None
    for _ in range(15):
        state, metrics = step(state, shard_batch(_batch(rng), mesh))
        if first is None:
            first = float(metrics["loss"])
    assert float(metrics["loss"]) < first * 0.7, (first, float(metrics["loss"]))


def test_moe_matches_unsharded(nano):
    """EP-sharded MoE loss == replicated MoE loss (the all-to-all is exact)."""
    cfg = GPTConfig.nano(dtype=jnp.float32, moe_experts=4)
    rng = np.random.default_rng(3)
    batch = _batch(rng)

    mesh_ep = MeshSpec(data=2, expert=4).build()
    opt = default_optimizer(learning_rate=1e-3)
    s1 = create_train_state(cfg, jax.random.PRNGKey(2), opt, mesh=mesh_ep)
    step1 = make_train_step(cfg, opt, mesh=mesh_ep)
    _, m1 = step1(s1, shard_batch(batch, mesh_ep))

    mesh_1 = MeshSpec(data=1).build(jax.devices()[:1])
    s2 = create_train_state(cfg, jax.random.PRNGKey(2), opt, mesh=mesh_1)
    step2 = make_train_step(cfg, opt, mesh=mesh_1)
    _, m2 = step2(s2, shard_batch(batch, mesh_1))

    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-4)


def test_llama_family_trains_sharded():
    """Llama family (RMSNorm/SwiGLU/RoPE/GQA): trains on a DP x TP mesh via
    the shared model factories; GQA kv heads stay replicated when they don't
    divide the tensor axis."""
    from ray_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig.nano(dtype=jnp.float32)
    mesh = MeshSpec(data=2, tensor=4).build()
    opt = default_optimizer(learning_rate=1e-2)
    state = create_train_state(cfg, jax.random.PRNGKey(0), opt, mesh=mesh)
    assert "tensor" in str(state.params["blocks"]["wq"].sharding.spec)
    step = make_train_step(cfg, opt, mesh=mesh)
    rng = np.random.default_rng(0)
    first = None
    for _ in range(20):
        state, metrics = step(state, shard_batch(_batch(rng), mesh))
        if first is None:
            first = float(metrics["loss"])
    assert float(metrics["loss"]) < first * 0.6, (first, float(metrics["loss"]))


def test_llama_pipeline_parity():
    """Llama pipelines through the shared stack scaffolding: PP loss == 1-dev."""
    from ray_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig.nano(dtype=jnp.float32)
    opt = default_optimizer(learning_rate=1e-3)
    rng = np.random.default_rng(5)
    batch = _batch(rng)

    meshp = MeshSpec(data=2, pipeline=2, tensor=2).build()
    sp = create_train_state(cfg, jax.random.PRNGKey(1), opt, mesh=meshp)
    _, mp = make_train_step(cfg, opt, mesh=meshp)(sp, shard_batch(batch, meshp))

    mesh1 = MeshSpec(data=1).build(jax.devices()[:1])
    s1 = create_train_state(cfg, jax.random.PRNGKey(1), opt, mesh=mesh1)
    _, m1 = make_train_step(cfg, opt, mesh=mesh1)(s1, shard_batch(batch, mesh1))

    np.testing.assert_allclose(float(mp["loss"]), float(m1["loss"]), rtol=1e-4)


def test_llama_num_params_matches_tree():
    from ray_tpu.models import llama

    cfg = llama.LlamaConfig.nano(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    actual = sum(p.size for p in jax.tree.leaves(params))
    assert actual == llama.num_params(cfg)


def test_llama_pipeline_context_parallel_rope_positions():
    """PP x CP Llama: RoPE tables ride the stack as context-sharded streams,
    so every CP shard rotates with GLOBAL positions — loss matches 1 device."""
    from ray_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig.nano(dtype=jnp.float32)
    opt = default_optimizer(learning_rate=1e-3)
    rng = np.random.default_rng(11)
    toks = _batch(rng)["tokens"]
    batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:]}

    meshpc = MeshSpec(data=2, pipeline=2, context=2).build()
    sp = create_train_state(cfg, jax.random.PRNGKey(3), opt, mesh=meshpc)
    _, mp = make_train_step(cfg, opt, mesh=meshpc)(sp, shard_batch(batch, meshpc))

    mesh1 = MeshSpec(data=1).build(jax.devices()[:1])
    s1 = create_train_state(cfg, jax.random.PRNGKey(3), opt, mesh=mesh1)
    _, m1 = make_train_step(cfg, opt, mesh=mesh1)(s1, shard_batch(batch, mesh1))

    np.testing.assert_allclose(float(mp["loss"]), float(m1["loss"]), rtol=1e-4)


def test_hf_gpt2_import_logit_parity():
    """HF GPT-2 weights convert to the zoo layout with exact forward parity
    (models/hf.py — the reference's HF fine-tune on-ramp, BASELINE config #4)."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    from ray_tpu.models.hf import load_hf_gpt2
    from ray_tpu.models import forward

    torch.manual_seed(0)
    hf = transformers.GPT2LMHeadModel(
        transformers.GPT2Config(
            vocab_size=130, n_positions=64, n_embd=32, n_layer=2, n_head=2
        )
    )
    hf.eval()
    cfg, params = load_hf_gpt2(hf, dtype=jnp.float32, attention="xla")
    assert cfg.vocab_size == 256  # 130 padded to a multiple of 128
    x = np.random.default_rng(0).integers(0, 130, (2, 16)).astype(np.int32)
    with torch.no_grad():
        ref = hf(torch.from_numpy(x.astype(np.int64))).logits.numpy()
    ours = np.asarray(forward(jax.tree.map(jnp.asarray, params), jnp.asarray(x), cfg))
    np.testing.assert_allclose(ours[:, :, :130], ref, atol=2e-5)


def test_hf_gpt2_finetune_on_mesh():
    """Imported HF weights fine-tune under a sharded mesh: loss decreases and
    every parallelism rule applies to the converted pytree unchanged."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    from ray_tpu.models.hf import load_hf_gpt2
    from ray_tpu.models import default_optimizer, make_train_step, shard_batch
    from ray_tpu.models.training import TrainState, param_shardings
    from ray_tpu.parallel import MeshSpec, ShardingRules

    torch.manual_seed(1)
    hf = transformers.GPT2LMHeadModel(
        transformers.GPT2Config(
            vocab_size=130, n_positions=64, n_embd=32, n_layer=2, n_head=2
        )
    )
    cfg, params = load_hf_gpt2(hf, dtype=jnp.float32, attention="xla")
    mesh = MeshSpec(data=2, tensor=4).build()
    shardings = param_shardings(cfg, mesh, ShardingRules())
    params = jax.tree.map(
        lambda p, s: jax.device_put(jnp.asarray(p), s), params, shardings
    )
    opt = default_optimizer(learning_rate=1e-3)
    state = TrainState(params=params, opt_state=jax.jit(opt.init)(params),
                       step=jnp.zeros((), jnp.int32))
    step = make_train_step(cfg, opt, mesh=mesh)
    rng = np.random.default_rng(0)
    toks = (rng.integers(0, 60, (8, 1)) + np.arange(33)) % 130
    batch = shard_batch({"tokens": toks.astype(np.int32)}, mesh)
    first = None
    for _ in range(25):
        state, m = step(state, batch)
        first = first or float(m["loss"])
    assert float(m["loss"]) < first - 0.5, (first, float(m["loss"]))


def test_resnet_forward_and_dp_training():
    """Vision family: ResNet (GroupNorm) forwards with correct shapes and
    trains data-parallel through the shared TrainState/step factory."""
    from ray_tpu.models import (
        ResNetConfig,
        create_train_state,
        default_optimizer,
        make_train_step,
        shard_batch,
    )
    from ray_tpu.models import resnet

    cfg = ResNetConfig.nano(dtype=jnp.float32)
    params = resnet.init_params(cfg, jax.random.PRNGKey(0))
    imgs = jnp.asarray(np.random.default_rng(0).standard_normal((4, 32, 32, 3)), jnp.float32)
    logits = resnet.forward(params, imgs, cfg)
    assert logits.shape == (4, 10) and logits.dtype == jnp.float32

    # 16x16 inputs + few steps: each step's 8 device programs serialize on
    # this box's core, and a slow step under load risks XLA CPU's collective
    # rendezvous watchdog (see conftest) — keep the per-step conv work small.
    mesh = MeshSpec(data=8).build()
    opt = default_optimizer(learning_rate=1e-2)
    state = create_train_state(cfg, jax.random.PRNGKey(0), opt, mesh=mesh)
    step = make_train_step(cfg, opt, mesh=mesh)
    rng = np.random.default_rng(0)
    # Learnable toy task: class = channel-0 brightness.
    labels = rng.integers(0, 10, (16,))
    images = rng.standard_normal((16, 16, 16, 3)).astype(np.float32) * 0.1
    for i, lb in enumerate(labels):
        images[i, :, :, 0] += lb * 0.3  # class signal in channel 0
    batch = shard_batch(
        {"images": images, "labels": labels.astype(np.int32)}, mesh
    )
    first = None
    for _ in range(30):
        state, m = step(state, batch)
        first = first or float(m["loss"])
    # ln(10)=2.3 at random init; memorizing 16 examples should cut it sharply.
    # 0.55 (not 0.5): optimizer numerics differ slightly across jax/jaxlib
    # versions — 0.4.x lands at ~0.52x after 30 steps, newer stacks below
    # 0.5x; the assertion is about sharp descent, not an exact constant.
    assert float(m["loss"]) < first * 0.55, (first, float(m["loss"]))


def test_resnet50_param_count():
    """ResNet-50 parameter count sanity (~25.6M torchvision equivalent; GN
    scale/bias replace BN running stats, same learnable count)."""
    from ray_tpu.models import ResNetConfig
    from ray_tpu.models import resnet

    n = resnet.num_params(ResNetConfig.resnet50())
    assert 25_000_000 < n < 26_100_000, n


def test_model_for_finds_the_module_that_defines_the_configuration():
    """No registry: a configuration's model is the module its class lives in,
    and an object that is no model's configuration is an error, not GPT."""
    from ray_tpu.models import LlamaConfig, OLMoEConfig, ResNetConfig, gpt, llama, olmoe, resnet
    from ray_tpu.models.training import model_for

    assert model_for(GPTConfig.nano()) is gpt
    assert model_for(LlamaConfig.nano()) is llama
    assert model_for(OLMoEConfig.nano()) is olmoe
    assert model_for(ResNetConfig()) is resnet
    for stranger in ({"n_layer": 2}, object(), MeshSpec(data=1)):
        with pytest.raises(TypeError, match="no model's configuration"):
            model_for(stranger)
    with pytest.raises(TypeError, match="no model's configuration"):
        create_train_state({"n_layer": 2}, jax.random.PRNGKey(0), default_optimizer())


@pytest.mark.parametrize("config", ["GPTConfig", "LlamaConfig", "OLMoEConfig"])
def test_the_remat_policy_changes_no_loss_and_no_gradient(config):
    """What `stack.block` recomputes is `config.remat` / `remat_policy`'s to
    say, for every model alike; what it computes is not: in f32 the loss and
    every gradient agree to rounding whichever parts run again."""
    from ray_tpu import models
    from ray_tpu.models.training import model_for

    tokens = jnp.asarray(_batch(np.random.default_rng(0), batch=2, seq=32)["tokens"])
    got = {}
    for policy in ("save_attn", "dots", "off"):
        cfg = getattr(models, config).nano(dtype=jnp.float32, attention="xla", remat=policy != "off",
                                           remat_policy=None if policy == "off" else policy)
        model = model_for(cfg)
        params = model.init_params(cfg, jax.random.PRNGKey(0))
        got[policy] = jax.jit(jax.value_and_grad(
            lambda p: model.loss_fn(p, {"tokens": tokens}, cfg)))(params)  # noqa: B023
    loss, grads = got["off"]
    assert np.isfinite(float(loss)) and float(loss) > 0
    for policy in ("save_attn", "dots"):
        np.testing.assert_allclose(got[policy][0], loss, rtol=1e-6)
        for a, b in zip(jax.tree.leaves(got[policy][1]), jax.tree.leaves(grads)):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
