"""The Solar-Open2 cell's kernels and its whole train step, compiled ahead of time for a `v5e:2x2`
(`tests/aot_v5e.py`, a process a list)."""

import json
import os
import re

import pytest

import aot_v5e
from benchmark.harness.program_trace import PHASES, phase

# The cell's step (PR 59): one period of (gqa, kda, kda, kda) in one scan on one chip, a row of 4,096. A linear
# layer's scan is two Mosaic calls (`kda_fwd`, `kda_bwd`), the attention layer's the two flash kernels with 8 query
# heads on one key/value head; the short convolutions' gradient is `short_conv_bwd` at heads of 128.
SOLAR = "solar-open2-250b-ep40-l4"
KDA_4K = "kda:1x8x4096x128x128"
V5E_HBM_BYTES = 16_909_336_064
PARAMETERS = 840_874_392
HELD_EXPERT_PARAMETERS = 503_316_480


@pytest.fixture(scope="module")
def aot():
    return aot_v5e.Cases([KDA_4K], ["step:" + SOLAR])


def test_the_scans_kernels_compile_for_the_v5e_at_the_cells_widths(aot):
    """(1, 8, 4096, 128) bf16 with an f32 gate as wide as the keys: a state of 128 x 128 f32 in VMEM scratch carried
    along the sequential axis, seven levels of the halving and the doubling unrolled in one body for two heads."""
    from ray_tpu.ops import gated_delta_rule as gdn

    got = aot[KDA_4K]
    assert got["mosaic_calls"] == 2 and got["kernels"] == ["kda_bwd", "kda_fwd"]
    heads = gdn.heads_per_program(8, 4096, gdn.CHUNK, 128, 128, 4)
    assert heads == 2 and {re.sub(r"\)+", "", plan) for plan in got["plans"]} == {f"chunk_{gdn.CHUNK}/heads_2of8"}
    assert got["states"] == [f"f32[8,{4096 // gdn.CHUNK},128,128]"]  # the state every chunk starts from, f32


def test_the_step_runs_each_kernel_once_a_layer_and_never_again_in_the_backward_pass(aot):
    got = aot["step:" + SOLAR]
    kernels = [(n.split("/")[-2], n) for n in got["mosaic_scopes"]]
    count = lambda name: sum(k == name for k, _ in kernels)  # noqa: E731
    assert (count("kda_fwd"), count("kda_bwd"), count("flash_fwd"), count("flash_bwd")) == (3, 3, 1, 1)
    assert count("short_conv_bwd") == 9  # three linear places x (q, k, v), the gradient alone
    for name, scope in kernels:
        parts = scope.split("/")
        if name.startswith("kda_"):  # the kernels' scope names the plan
            assert parts[-4:-2] == ["chunk_128", "heads_2of8"] and "kda" in parts, scope
        if name.startswith(("kda_", "flash_")):
            assert phase(scope) == ("backward" if name.endswith("_bwd") else "forward")
            assert "rematted_computation" not in parts and "attention" in parts  # `save_attn`
        if name == "short_conv_bwd":
            assert "kda" in parts and "kda_conv" in parts and phase(scope) == "backward"
    # The expert layers run the grouped-matmul kernels over the held prefix in both forms of the layer.
    assert {"gmm_fwd", "gmm_dlhs", "gmm_drhs", "sum_rows"} <= {k for k, _ in kernels}
    assert got["phases"] == sorted(PHASES)
    assert got["element_moves"]["scalars"] == [] and got["backward_scatter_adds"] == []


def test_the_step_fits_the_chip_with_nine_tenths_of_a_gigabyte_to_spare(aot):
    """840.9 M parameters x 12 B and the 503.3 M held expert parameters' bf16 copy (PR 64) are the arguments (the
    gradient is a temporary), XLA's peak is under 16.0 GB of the chip's 16.91 and over the contract's floor, and
    what the file records is this compile's less the copy. Pinned again at PR 64, on purpose: the step is unrolled,
    so the copy's 1,006,632,960 B are paid back by the casts that are no temporaries any more and by nothing else:
    the peak is 14,820,838,912 where the parent's was 14,065,864,192."""
    got = aot["step:" + SOLAR]
    assert got["compute_copy_bytes"] == HELD_EXPERT_PARAMETERS * 2
    state = got["argument"] - got["compute_copy_bytes"]
    assert 0 <= state - PARAMETERS * 12 < 16 << 20  # beside the state: step, counts, the batch
    assert got["peak"] is not None and 0.25 * V5E_HBM_BYTES < got["peak"] <= 16.0e9
    with open(os.path.join(aot_v5e.REPO, "benchmark", "configs", SOLAR + ".json")) as fh:
        recorded = json.load(fh)["memory_analysis_v5e_bytes"]
    assert state <= recorded["arguments"] and got["peak"] - got["compute_copy_bytes"] <= recorded["peak"]
    assert got["remat_products"] == 0 and got["recomputed"] <= 358  # the parent's


def test_nothing_of_the_logits_size_stands_beside_the_logits(aot):
    """4,096 x 24,576: until PR 68 `fusion.491`, `copy.811`, `fusion.63` and `reshape.697` beside the head's product;
    26,287 instructions for 26,396, the peak the same. (The tables and their gradients, 24,576 x 4,096, are as long and
    are not the loss's: `aot_v5e.logits_sized` leaves an argument's dimensions out; ISSUE 68's one bf16 array.)"""
    aot_v5e.holds_the_logits_alone(aot["step:" + SOLAR])


def test_no_pass_rounds_an_expert_matrix_outside_the_optimizer(aot):
    """The parent's step cast each of the twelve matrices forward and again backward, in both forms of a layer."""
    aot_v5e.rounds_the_experts_matrices_in_the_optimizer_alone(aot["step:" + SOLAR], 48)
