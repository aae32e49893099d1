"""`stack.cross_entropy` picks the target's logit by a compare and a sum (PR 68): a gather's gradient is a scatter
into zeros of the logits' shape, which a step of one row a device paid for with four logits-sized arrays. Value
and gradient against `-log_softmax` gathered in float32 (the independent side keeps its `take_along_axis`), for
the function and for `causal_lm_loss`'s three forms; and the gradient's jaxpr holds no scatter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.stack import causal_lm_loss, cross_entropy

S, V = 64, 1000
SHAPES = {"one_row": (1, S), "three_rows": (3, S)}


def reference(logits, targets):
    """Each position's -log softmax at its target, (B, S) f32."""
    return -jnp.take_along_axis(jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1), targets[..., None], -1)[..., 0]


def drawn(shape, seed=0):
    k_logits, k_targets = jax.random.split(jax.random.PRNGKey(seed))
    return 3.0 * jax.random.normal(k_logits, (*shape, V), jnp.float32), jax.random.randint(k_targets, shape, 0, V)


def plain(shape):
    return drawn(shape)


def ends(shape):
    """Every target the first column or the last."""
    logits, targets = drawn(shape, 1)
    return logits, jnp.where(targets % 2 == 0, 0, V - 1)


def equal_row(shape):
    """The first row's logits all equal (the entropy is log V wherever the target), its first position's all 0."""
    logits, targets = drawn(shape, 2)
    return logits.at[0].set(7.5).at[0, 0].set(0.0), targets


def minus_inf(shape):
    """-inf in a third of the columns, never in a position's target column."""
    logits, targets = drawn(shape, 3)
    off = (jnp.arange(V) % 3 == 0) & (jnp.arange(V) != targets[..., None])
    return jnp.where(off, -jnp.inf, logits), targets


INPUTS = {"plain": plain, "ends": ends, "equal_row": equal_row, "minus_inf": minus_inf}

# (the form's keyword arguments from the targets' shape, the same objective of the reference's entropies)
mask_of = lambda shape: (jnp.arange(shape[0] * shape[1]).reshape(shape) % 5) != 0  # noqa: E731
weights_of = lambda shape: 1.0 / (1.0 + jnp.arange(shape[1], dtype=jnp.float32) % 7) * jnp.ones(shape)  # noqa: E731
FORMS = {
    "plain": (lambda shape: {}, lambda ce, shape: ce.mean()),
    "mask": (lambda shape: {"mask": mask_of(shape)},
             lambda ce, shape: jnp.where(mask_of(shape), ce, 0.0).sum() / mask_of(shape).sum()),
    "weights": (lambda shape: {"weights": weights_of(shape)},
                lambda ce, shape: (weights_of(shape) * ce).sum() / ce.size),
}


def close(got, want, what):
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all(), f"{what}: not finite"
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6, err_msg=what)


@pytest.mark.parametrize("rows", SHAPES)
@pytest.mark.parametrize("inputs", INPUTS)
def test_cross_entropy_is_the_gathered_log_softmax(inputs, rows):
    logits, targets = INPUTS[inputs](SHAPES[rows])
    close(cross_entropy(logits, targets), reference(logits, targets), "value")
    # A cotangent of its own a position, so a position's gradient is told from its neighbour's.
    g = 1.0 + jnp.arange(targets.size, dtype=jnp.float32).reshape(targets.shape) / targets.size
    got = jax.grad(lambda x: (g * cross_entropy(x, targets)).sum())(logits)
    close(got, jax.grad(lambda x: (g * reference(x, targets)).sum())(logits), "gradient")
    if inputs == "equal_row":
        close(cross_entropy(logits, targets)[0], np.full(S, np.log(V), np.float32), "a row of equal logits")


@pytest.mark.parametrize("rows", SHAPES)
@pytest.mark.parametrize("inputs", ["plain", "minus_inf"])
@pytest.mark.parametrize("form", FORMS)
def test_causal_lm_loss_in_its_three_forms(form, inputs, rows):
    shape = SHAPES[rows]
    logits, targets = INPUTS[inputs](shape)
    kwargs, objective = FORMS[form]
    ours = lambda x: causal_lm_loss(x, targets, **kwargs(shape))  # noqa: E731
    theirs = lambda x: objective(reference(x, targets), shape)  # noqa: E731
    close(ours(logits), theirs(logits), "value")
    close(jax.grad(ours)(logits), jax.grad(theirs)(logits), "gradient")


def primitives(jaxpr):
    """The names of every primitive of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from primitives(inner)


@pytest.mark.parametrize("rows", SHAPES)
@pytest.mark.parametrize("form", FORMS)
def test_the_gradient_holds_no_scatter(form, rows):
    """The counter that says the mechanism engaged, chip-free: the gather's gradient was a `scatter-add` into
    zeros of the logits' shape. The reference's holds one, so the reader would see it."""
    shape = SHAPES[rows]
    logits, targets = plain(shape)
    kwargs, objective = FORMS[form]
    names = lambda f: set(primitives(jax.make_jaxpr(jax.grad(f))(logits).jaxpr))  # noqa: E731
    ours = names(lambda x: causal_lm_loss(x, targets, **kwargs(shape)))
    assert not {n for n in ours if "scatter" in n or "gather" in n}, sorted(ours)
    assert any("scatter" in n for n in names(lambda x: objective(reference(x, targets), shape)))


def test_a_target_outside_the_vocabulary_reads_no_logit():
    """The entropy is `lse` there (no column hit), the logits' gradient the softmax alone: finite, where the
    gather read NaN past the end."""
    logits, _ = plain((1, 4))
    targets = jnp.array([[V, -1, 2 * V, 5]])
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    close(cross_entropy(logits, targets)[0, :3], lse[0, :3], "value")
    close(jax.grad(lambda x: cross_entropy(x, targets).sum())(logits)[0, :3], jax.nn.softmax(logits, -1)[0, :3], "gradient")
