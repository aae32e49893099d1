"""What `benchmark/models/sdar.py check` can see, through `check` itself and at nano size on the CPU: the
reference computed a precision below the stated one and the program under a planted fault of the mask, each in
the program's place as `tools/sdar_readings.py` puts them there on the chip at the published widths; and the
draw held to its definition. (The comparison's other cases are in `tests/test_sdar.py`.)"""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import shared_checks  # noqa: E402
from benchmark.harness.manifest import Manifest  # noqa: E402
from benchmark.models import sdar as bench  # noqa: E402


@pytest.fixture(scope="module")
def nano():
    return Manifest().config("sdar-nano")


@pytest.fixture(scope="module")
def tokens():
    import jax.numpy as jnp

    return jnp.asarray(np.random.default_rng(0).integers(0, 254, (2, 33), dtype=np.int32))


@pytest.fixture(scope="module")
def bf16(nano):
    return bench.build(nano, None, 3)


@pytest.fixture(scope="module")
def check():
    return shared_checks.Checked(bench)


@pytest.fixture(scope="module")
def readings():
    """`tools/sdar_readings.py`, whose sides are what the chip run reads at the published widths."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("sdar_readings", os.path.join(REPO, "tools", "sdar_readings.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def system(bf16, check, tokens):
    """`check`'s summary of the program itself: what every other side is held against."""
    return check(bf16, tokens)


@pytest.mark.parametrize("side", ["below", "block8", "nonstrict"])
def test_through_check_a_lower_precision_and_a_wrong_mask_come_out_not_correct(nano, bf16, system, tokens, readings, side):
    """Each in the program's place, through `check` itself as the chip run does it (`tools/sdar_readings.py`):
    the reference in the precision below the stated one, and the program under blocks of 8 for 4 or with the
    strict quadrant read as the other. Element by element against the reference at the stated precision, the
    system stands orders under each: the cross entropies tell the precision, W_q's and W_k's gradients the mask.
    (A tile pair dropped from a schedule exists only where the kernels run: that side is read on the chip.)"""
    assert system["ok"] and system["ce_abs_err_mean_stated"] < 1e-5 and system["qk_grad_rel_dist_stated"] < 1e-2
    if side == "below":  # the toy's own limit is the published widths' in kind, not in size: held here by the ratio
        out = bench.check(bf16, tokens, program=bench.reference_program(nano, "below"),
                          ce_stated_abs_mean=30 * system["ce_abs_err_mean_stated"])
        assert out["ce_abs_err_mean_stated"] > 100 * system["ce_abs_err_mean_stated"], out
        assert out["over_limit"] == ["ce_stated_abs_mean"] and not out["ok"]
    else:
        out = readings.read(bench, bf16, tokens, side)
        assert not out["ok"] and "qk_grad_stated_rel" in out["over_limit"], out
        assert out["qk_grad_rel_dist_stated"] > 30 * system["qk_grad_rel_dist_stated"]
    assert readings.flash.BlockDiffusion is readings.BlockDiffusion  # the plant is gone with the call


def test_the_reference_at_the_stated_precision_is_its_own_and_the_draw_is_held_to_its_definition(nano, bf16, tokens):
    import jax

    from ray_tpu.models import sdar as model

    out = bench.check(bf16, tokens, program=bench.reference_program(nano, "stated"))
    assert out["ce_abs_err_mean_stated"] == 0 and out["qk_grad_rel_dist_stated"] == 0 and out["ok"]
    row = np.asarray(tokens[:, :-1])
    noised, masked, weight = (np.asarray(x) for x in model.noise(tokens[:, :-1], jax.random.PRNGKey(0), bf16.cfg))
    assert bench.draw_faults(nano, row, noised, masked, weight) == []
    big = {**nano, "noise_eps": 1e-3}
    rows = np.zeros((1, 8192), np.int32)
    noised, masked, weight = (np.asarray(x) for x in model.noise(rows, jax.random.PRNGKey(0), bf16.cfg))
    assert bench.draw_faults(big, rows, noised, masked, weight) == []
    assert masked.mean() == 0.484375  # the cell's `block_diffusion.masked_share`: the check's one draw, every run
    wrong = {"the complement masked": (np.where(~masked, nano["mask_token_id"], rows), ~masked, np.where(~masked, weight.max(), 0)),
             "1 / t squared": (noised, masked, weight ** 2), "a weight a token": (noised, masked, weight * (1 + np.arange(8192) % 2)),
             "another id": (np.where(masked, 7, rows), masked, weight), "unweighted": (noised, masked, masked * 1.0)}
    for name, draw in wrong.items():
        assert bench.draw_faults(big, rows, *draw), name
