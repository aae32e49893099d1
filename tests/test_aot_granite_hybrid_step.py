"""The granite-4.0-h-micro cell's kernels and its whole train step, compiled ahead of time for a `v5e:2x2`
(`tests/aot_v5e.py`, a process a list)."""

import json
import os
import re

import pytest

import aot_v5e
from benchmark.harness.program_trace import PHASES, phase

# The cell's step (PR 73): one period of ten layers unrolled in one scan on one chip, a row of 4,096. A mamba layer's scan
# is two Mosaic calls (`ssd_fwd`, `ssd_bwd`) on 64 heads of 64 that read one B and one C of 128, the attention layer's
# the two flash kernels with 32 query heads on 8 key/value heads of 64; the convolution's gradient is `short_conv_bwd`
# over 4,352 channels as 68 heads of 64, its bias's gradient a fifth row of the taps' block.
GRANITE = "granite-4.0-h-micro-l10"
SSD_4K = "ssd:1x64x1x4096x128x64"
V5E_HBM_BYTES = 16_909_336_064
PARAMETERS = 772_160_448


@pytest.fixture(scope="module")
def aot():
    return aot_v5e.Cases([SSD_4K], ["step:" + GRANITE])


def test_the_scans_kernels_compile_for_the_v5e_at_the_cells_widths(aot):
    """(1, 64, 4096, 64) bf16 values under one (1, 1, 4096, 128) B and C: states of 128 x 64 f32 for all 64 heads in
    VMEM scratch, four heads a program on the group's blocks, and no copy of B or C a head in HBM."""
    from ray_tpu.ops import ssd

    got = aot[SSD_4K]
    assert got["mosaic_calls"] == 2 and got["kernels"] == ["ssd_bwd", "ssd_fwd"]
    assert {re.sub(r"\)+", "", plan) for plan in got["plans"]} == {f"chunk_{ssd.CHUNK}/heads_{ssd.HEADS}of64/group_64"}
    assert got["states"] == [f"f32[64,{4096 // ssd.CHUNK},128,64]"]  # the state every chunk starts from, f32
    assert got["keys_a_head"] == []  # nothing of B's or C's shape times 64 heads


def test_the_step_runs_each_kernel_once_a_layer_and_never_again_in_the_backward_pass(aot):
    got = aot["step:" + GRANITE]
    kernels = [(n.split("/")[-2], n) for n in got["mosaic_scopes"]]
    count = lambda name: sum(k == name for k, _ in kernels)  # noqa: E731
    assert (count("ssd_fwd"), count("ssd_bwd"), count("flash_fwd"), count("flash_bwd")) == (9, 9, 1, 1)
    assert count("short_conv_bwd") == 9 and len(kernels) == 29  # nine mamba places, the gradient alone
    for name, scope in kernels:
        parts = scope.split("/")
        if name.startswith("ssd_"):  # the kernels' scope names the plan, under the mixer's and the scan's
            assert parts[-5:-2] == ["chunk_128", "heads_4of64", "group_64"] and parts[-7:-5] == ["ssd", "ssd_scan"], scope
        if name.startswith(("ssd_", "flash_")):
            assert phase(scope) == ("backward" if name.endswith("_bwd") else "forward")
            assert "rematted_computation" not in parts and "attention" in parts  # `save_attn`
        if name == "short_conv_bwd":
            assert parts[-6:-2] == ["ssd", "ssd_conv", "tile_128", "rows_4096"] and phase(scope) == "backward"
    assert got["phases"] == sorted(PHASES)


def test_the_step_fits_the_chip_with_the_room_the_file_states(aot):
    """772.2 M parameters x 12 B are the arguments (the gradient is a temporary, no compute copy: no kernel's grouped
    operand), XLA's peak is under 15.9 GB of the chip's 16.91 and over the contract's floor, and the file records it."""
    got = aot["step:" + GRANITE]
    assert got["compute_copy_bytes"] == 0
    assert 0 <= got["argument"] - PARAMETERS * 12 < 16 << 20  # beside the state: step, counts, the batch
    assert got["peak"] is not None and 0.25 * V5E_HBM_BYTES < got["peak"] <= 15.9e9
    with open(os.path.join(aot_v5e.REPO, "benchmark", "configs", GRANITE + ".json")) as fh:
        recorded = json.load(fh)["memory_analysis_v5e_bytes"]
    assert got["argument"] <= recorded["arguments"] and got["peak"] <= recorded["peak"] < V5E_HBM_BYTES


def test_nothing_of_the_logits_size_stands_beside_the_logits(aot):
    """4,096 x 12,544 f32 once, the head's product against the tied table; the division by 8 is fused into it."""
    aot_v5e.holds_the_logits_alone(aot["step:" + GRANITE])
