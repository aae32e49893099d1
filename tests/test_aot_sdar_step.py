"""The SDAR cell's whole train step, compiled ahead of time for a `v5e:2x2`
(`tests/aot_v5e.py`, a process of its own)."""

import pytest

import aot_v5e
from benchmark.harness.program_trace import PHASES, phase

# The SDAR cell's step (PR 47): what its five layers, one scan, hand to Mosaic. The two flash kernels a (Q tile, K
# tile) pair a program over the 160 of 512 pairs of 512 x 1,024 that the block-diffusion mask leaves, and the
# held-prefix expert layer's kernels; no selection, no indexer.
SDAR = "sdar-30b-a3b-chat-ep8"
V5E_HBM_BYTES = 16_909_336_064


@pytest.fixture(scope="module")
def aot():
    return aot_v5e.steps(SDAR)


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd"])
def test_the_sdar_step_hands_mosaic_the_streamed_kernels_under_the_masks_schedule(aot, kernel):
    got = aot(SDAR)
    (scope,) = [n for n in got["mosaic_scopes"] if n.split("/")[-2] == kernel]
    assert phase(scope) == ("backward" if kernel == "flash_bwd" else "forward")
    parts = scope.split("/")
    assert "attention" in parts and "rematted_computation" not in parts  # `save_attn`: the kernels run once a layer
    assert "tiles_160of512" in parts and ("group_8" in parts) == (kernel == "flash_fwd")
    assert "keys_1152of1280" in parts  # both score a crossed pair over its live span of keys (PR 48)


def test_the_sdar_step_fits_the_chip_and_names_its_phases_and_the_draw(aot):
    """551.0 M parameters x 12 B of arguments (the f32 gradient is a temporary), and XLA's peak under the chip's
    16.91 GB, under the Keye cell's too: the same positions a layer with no selection and no indexer's residuals."""
    got = aot(SDAR)
    assert 0 <= got["argument"] - 550_984_960 * 12 < 1 << 20  # the state; beside it the step, the counts, the batch, padding
    assert got["peak"] is None or got["peak"] < 0.95 * V5E_HBM_BYTES
    assert got["phases"] == sorted(PHASES)
    moe = sorted({n.split("/")[-2] for n in got["mosaic_scopes"]} - {"flash_fwd", "flash_bwd"})
    assert moe == ["gmm_dlhs", "gmm_drhs", "gmm_fwd", "sum_rows"]  # (XLA's gather out of 16,384 rows: `moe._rows_by`)
