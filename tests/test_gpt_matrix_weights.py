"""`models/gpt.py` stores its attention weights as matrices (PR 30): `qkv_w`
(L, d, 3*nh*hd) in HF's `c_attn` order and `out_w` (L, nh*hd, d), where the
parent held (L, d, 3, nh, hd) and (L, nh, hd, d).

Same numbers, same order: a reshape maps one tree onto the other, and the
model computes what the parent's formula computes, on one device and on the
virtual `fsdp=4` mesh, where the block asks for each matrix whole before it
splits the heads out (`_whole_over`). The parent's formula is written out
here, on the parent's shapes, and shares nothing with the model but the
parameters.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import GPTConfig, gpt, init_params, loss_fn
from ray_tpu.models.training import param_shardings
from ray_tpu.parallel import MeshSpec, ShardingRules


@pytest.fixture(scope="module")
def nano():
    return GPTConfig.nano(dtype=jnp.float32, attention="xla")


@pytest.fixture(scope="module")
def params(nano):
    p = init_params(nano, jax.random.PRNGKey(7))
    # Biases and the output projection away from their zero / tiny init, so
    # that every leaf's gradient says something.
    keys = iter(jax.random.split(jax.random.PRNGKey(8), 32))
    blocks = {k: v + 0.02 * jax.random.normal(next(keys), v.shape, v.dtype)
              for k, v in p["blocks"].items()}
    return {**p, "blocks": blocks}


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(0).integers(0, 256, (8, 33)), jnp.int32)


def parent_shapes(tree, cfg):
    """The tree as the parent stored it."""
    L, d, nh, hd = cfg.n_layer, cfg.d_model, cfg.n_head, cfg.head_dim
    blocks = dict(tree["blocks"])
    blocks["qkv_w"] = blocks["qkv_w"].reshape(L, d, 3, nh, hd)
    blocks["out_w"] = blocks["out_w"].reshape(L, nh, hd, d)
    return {**tree, "blocks": blocks}


def parent_loss(p, tokens, cfg):
    """GPT-2's loss by the parent's formula (`bsd,dcnh->bscnh` and
    `bnsh,nhd->bsd` on 5-D and 4-D weights), a Python loop over the layers,
    plain softmax attention, no remat."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    S = inputs.shape[1]

    def ln(x, scale, bias):
        mean = x.mean(-1, keepdims=True)
        var = ((x - mean) ** 2).mean(-1, keepdims=True)
        return (x - mean) * jax.lax.rsqrt(var + 1e-5) * scale + bias

    x = p["wte"][inputs] + p["wpe"][:S][None]
    causal = jnp.tril(jnp.ones((S, S), bool))
    for i in range(cfg.n_layer):
        w = jax.tree.map(lambda a: a[i], p["blocks"])
        h = ln(x, w["ln1_scale"], w["ln1_bias"])
        qkv = jnp.einsum("bsd,dcnh->bscnh", h, w["qkv_w"]) + w["qkv_b"]
        q, k, v = (jnp.moveaxis(qkv[:, :, c], 2, 1) for c in range(3))
        scores = jnp.einsum("bnqh,bnkh->bnqk", q, k) / np.sqrt(cfg.head_dim)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        o = jnp.einsum("bnqk,bnkh->bnqh", probs, v)
        x = x + jnp.einsum("bnsh,nhd->bsd", o, w["out_w"]) + w["out_b"]
        h = ln(x, w["ln2_scale"], w["ln2_bias"])
        h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", h, w["fc_w"]) + w["fc_b"])
        x = x + jnp.einsum("bsf,fd->bsd", h, w["proj_w"]) + w["proj_b"]
    logits = jnp.einsum("bsd,vd->bsv", ln(x, p["lnf_scale"], p["lnf_bias"]), p["wte"])
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    return (lse - jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]).mean()


def test_the_weights_are_stored_as_matrices_in_hfs_order(nano, params):
    L, d = nano.n_layer, nano.d_model
    shapes = {k: v.shape for k, v in params["blocks"].items()}
    assert shapes["qkv_w"] == (L, d, 3 * d) and shapes["out_w"] == (L, d, d)
    assert shapes["qkv_b"] == (L, 3, nano.n_head, nano.head_dim)
    axes = gpt.param_logical_axes(nano)["blocks"]
    # FSDP shards `embed` as it did; the flat q|k|v columns do not split by
    # head, so `tensor` leaves them whole; `out_w`'s rows are head-major.
    assert axes["qkv_w"] == ("layers", "embed", None)
    assert axes["out_w"] == ("layers", "heads", "embed")
    assert all(len(axes[k]) == len(shapes[k]) for k in shapes)


@pytest.mark.parametrize("mesh_axes", [None, {"fsdp": 4}], ids=["one_device", "fsdp4"])
def test_loss_and_gradients_are_the_parents_formulas(nano, params, tokens, mesh_axes):
    want_loss, want = jax.jit(jax.value_and_grad(lambda p: parent_loss(p, tokens, nano)))(
        parent_shapes(params, nano))
    mesh = None
    if mesh_axes:
        mesh = MeshSpec(**mesh_axes).build(jax.devices()[:4])
        shardings = param_shardings(nano, mesh, ShardingRules())
        assert "fsdp" in str(shardings["blocks"]["qkv_w"].spec)
        assert "fsdp" in str(shardings["blocks"]["out_w"].spec)
        params = jax.tree.map(jax.device_put, params, shardings)
    got_loss, got = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, {"tokens": tokens}, nano, mesh=mesh)))(params)
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=1e-5)
    got = parent_shapes(got, nano)  # gradients map by the same reshape
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        assert g.shape == w.shape, path
        assert float(jnp.abs(w).max()) > 0, path
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=2e-6 * float(jnp.abs(w).max()) + 1e-8, err_msg=str(path))


def test_the_fsdp_program_gathers_the_matrices_before_the_heads_are_split(tokens):
    """On the CPU mesh too, the all-gathers of the attention weights are of
    2-D (per-layer) matrices: no gathered operand has head_dim as a dimension
    of its own."""
    import re

    mesh = MeshSpec(fsdp=4).build(jax.devices()[:4])
    cfg = GPTConfig.nano(attention="xla")
    shardings = param_shardings(cfg, mesh, ShardingRules())
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    abstract = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                            shapes, shardings)
    text = jax.jit(jax.grad(lambda p: loss_fn(p, {"tokens": tokens}, cfg, mesh=mesh))).lower(
        abstract).compile().as_text()
    gathered = re.findall(r"= \w+\[([\d,]+)\][^ ]* all-gather\(", text)
    assert gathered, "no all-gather in the fsdp=4 program"
    for dims in (tuple(int(n) for n in g.split(",")) for g in gathered):
        assert cfg.head_dim not in dims and len([n for n in dims if n > 1]) <= 2, dims


def test_an_old_shape_tree_loads_by_a_reshape(nano, params):
    old = parent_shapes(params, nano)
    assert old["blocks"]["qkv_w"].ndim == 5 and old["blocks"]["out_w"].ndim == 4
    new = gpt.stored_form(old)
    assert jax.tree.structure(new) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(params)):
        assert a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # Already in the stored form: untouched, and numpy trees (a restored
    # checkpoint before `device_put`) go through the same way.
    again = gpt.stored_form(params)
    assert all(a is b for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(params)))
    as_numpy = gpt.stored_form(jax.tree.map(np.asarray, old))
    assert as_numpy["blocks"]["qkv_w"].shape == params["blocks"]["qkv_w"].shape
    # AdamW's moments are laid out like the parameters.
    import optax

    moments = optax.adam(1e-3).init(old)[0]
    assert gpt.stored_form(moments.mu)["blocks"]["out_w"].shape == params["blocks"]["out_w"].shape


def test_hf_import_takes_c_attn_and_c_proj_as_they_are():
    """`load_hf_gpt2` stacks `c_attn.weight` (d, 3d) and `c_proj.weight`
    (d, d) without a reshape, and they come back out of the tree the same."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    from ray_tpu.models.hf import load_hf_gpt2

    torch.manual_seed(2)
    hf = transformers.GPT2LMHeadModel(transformers.GPT2Config(
        vocab_size=130, n_positions=64, n_embd=32, n_layer=2, n_head=2))
    cfg, params = load_hf_gpt2(hf, dtype=jnp.float32, attention="xla")
    sd = {k: v.detach().numpy() for k, v in hf.state_dict().items()}
    for i in range(cfg.n_layer):
        for ours, theirs in (("qkv_w", "c_attn"), ("out_w", "c_proj")):
            want = sd[f"transformer.h.{i}.attn.{theirs}.weight"]
            got = params["blocks"][ours][i]
            assert got.shape == want.shape
            np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(params["blocks"]["qkv_b"][i].reshape(-1),
                                      sd[f"transformer.h.{i}.attn.c_attn.bias"])
    assert jax.tree.map(np.shape, params) == jax.tree.map(
        np.shape, init_params(cfg, jax.random.PRNGKey(0)))
