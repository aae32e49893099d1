"""The plain float32 reference against `ray_tpu.models.gpt`, at nano size on
the CPU; on the chip the same comparison runs at the published widths."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness.manifest import Manifest  # noqa: E402
from benchmark.models import gpt2  # noqa: E402


@pytest.fixture(scope="module")
def nano():
    return Manifest().config("gpt2-nano")


@pytest.fixture(scope="module")
def tokens():
    import jax.numpy as jnp

    return jnp.asarray(np.random.default_rng(0).integers(0, 250, (2, 65), dtype=np.int32))


def test_the_system_agrees_with_the_reference(nano, tokens):
    got = gpt2.check(gpt2.build(nano, None, 0), tokens)
    assert got["ok"], got
    assert got["loss_abs_err"] < 1e-3 and abs(got["loss_reference"] - np.log(256)) < 0.1


def test_in_float32_they_agree_to_rounding(nano, tokens):
    """The reference computes the system's function, not one near it."""
    got = gpt2.check(gpt2.build(dict(nano, dtype="float32"), None, 0), tokens)
    assert got["loss_abs_err"] < 2e-6 and got["grad_norm_rel_err"] < 2e-5, got


def test_parameters_kept_in_bf16_fail_the_check(nano, tokens):
    import jax
    import jax.numpy as jnp

    system = gpt2.build(nano, None, 0)
    system.state.params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), system.state.params)
    got = gpt2.check(system, tokens)
    assert not got["ok"] and got["state_dtypes_other_than_stated"] == ["bfloat16"]


@pytest.fixture(scope="module")
def trained(nano, tokens):
    """Weights that mean something: at seeded initial weights the loss hardly
    depends on what attention does."""
    system = gpt2.build(dict(nano, learning_rate=3e-3), None, 0)
    for _ in range(60):
        system.state, metrics = system.step(system.state, {"tokens": tokens})
    assert float(metrics["loss"]) < 4.0
    return system


def test_they_agree_at_trained_weights_too(trained, tokens):
    got = gpt2.check(trained, tokens)
    assert got["ok"], got


def test_another_function_fails_the_check(trained, tokens, monkeypatch):
    """A system whose attention sees the future is far outside the tolerance."""
    import ray_tpu.models as models
    from ray_tpu.ops.flash_attention import xla_attention

    real = models.loss_fn

    def leaky(params, batch, cfg, **kw):
        return real(params, batch, cfg, attention_fn=lambda q, k, v: xla_attention(
            q, k, v, causal=False), **kw)

    monkeypatch.setattr(models, "loss_fn", leaky)
    got = gpt2.check(trained, tokens)
    assert not got["ok"] and got["loss_abs_err"] > 10 * gpt2.LOSS_ABS_TOL, got
