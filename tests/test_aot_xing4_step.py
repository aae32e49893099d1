"""The Xing4.0 cell's whole train step, compiled ahead of time for a `v5e:2x2` (`tests/aot_v5e.py`, a process of its
own), beside `tests/test_aot_trinity_step.py`: what Mosaic is handed, what the chip holds, what a layer saves."""

import json
import os

import pytest

import aot_v5e
from benchmark.harness.program_trace import PHASES, phase

XING4 = "xing4-29b-a4b-ep8-l5"
V5E_HBM_BYTES = 16_909_336_064
PARAMETERS = 759_346_446
HELD_EXPERT_PARAMETERS = 4 * 8 * 3 * 3584 * 1024


@pytest.fixture(scope="module")
def aot():
    return aot_v5e.steps(XING4)


def test_the_step_hands_mosaic_both_flash_kernels_a_layer_at_two_widths(aot):
    """A leading dense layer and a scan over four expert layers: both flash kernels once a layer (the unrolled layer's
    and the scan body's), under the stack's scope `attention`, walking the triangle's 36 of 64 pairs of 512; never
    again in the backward pass (`save_attn`). The expert layers run the grouped-matmul kernels over the held prefix."""
    got = aot(XING4)
    flash = [n.split("/") for n in got["mosaic_scopes"] if n.split("/")[-2] in ("flash_fwd", "flash_bwd")]
    for kernel in ("flash_fwd", "flash_bwd"):
        mine = [p for p in flash if p[-2] == kernel]
        assert len(mine) == 2 and all("attention" in p and "rematted_computation" not in p for p in mine)
        assert all(phase("/".join(p)) == ("backward" if kernel == "flash_bwd" else "forward") for p in mine)
    assert all("tiles_36of64" in p for p in flash if p[-2] == "flash_fwd")
    assert {"gmm_fwd", "gmm_dlhs", "gmm_drhs", "sum_rows"} <= {n.split("/")[-2] for n in got["mosaic_scopes"]}
    assert got["phases"] == sorted(PHASES)
    assert got["element_moves"]["scalars"] == [] and got["backward_scatter_adds"] == []


def test_the_mixes_backward_rules_are_two_mosaic_kernels_a_sublayer(aot):
    """`mhc_post_bwd` and `mhc_pre_bwd` once a sublayer, twice a layer, in the unrolled layer and in the scan's body:
    under the scope `mhc` (`post`, and `pre` inside the `maps` that called the product), in the backward pass, and
    never in what a checkpoint runs again (the rules keep the arrays they were handed). That the mechanism engages."""
    mine = [n.split("/") for n in aot(XING4)["mosaic_scopes"] if n.split("/")[-2].startswith("mhc_")]
    for kernel, scope in (("mhc_post_bwd", "post"), ("mhc_pre_bwd", "pre")):
        calls = [p for p in mine if p[-2] == kernel]
        assert len(calls) == 4 and sum("while" in p for p in calls) == 2, kernel  # two in the scan's body, two unrolled
        for p in calls:
            assert "mhc" in p and scope in p and "tile_128" in p and "rematted_computation" not in p, p
            assert phase("/".join(p)) == "backward", p
    assert len(mine) == 8
    assert all("maps" in p for p in mine if p[-2] == "mhc_pre_bwd")  # `mhc.maps_ms` keeps the product's gradient


def test_the_step_fits_the_chip_without_a_memory_lever(aot):
    """759.3 M parameters x 12 B and the 352.3 M held expert parameters' bf16 copy are the arguments (the gradient is a
    temporary); XLA's peak stands inside ISSUE 66's 15.5 GB of the chip's 16.91 with every head and `save_attn`, and
    since PR 67 under 15.0 (the rules' backward keeps no float32 copy of the streams: 14,987,682,816 B). Restated at
    PR 68, on purpose: without the loss's four arrays XLA's rematerialization clones one product where it cloned three
    (`fusion.1392.remat` alone: the head's `fusion.2183.remat`, 3.33 ms a step, and `fusion.1469.remat` are gone), and
    what it no longer makes twice it holds: 15,004,393,472 B, 4.4 MB over 15.0e9 and under the recorded 15,146,253,312;
    so the line holds the clones to one and the peak to 15.01e9."""
    got = aot(XING4)
    assert got["compute_copy_bytes"] == HELD_EXPERT_PARAMETERS * 2 == 704_643_072
    state = got["argument"] - got["compute_copy_bytes"]
    assert 0 <= state - PARAMETERS * 12 < 16 << 20  # beside the state: step, counts, the batch
    assert got["peak"] is not None and 0.25 * V5E_HBM_BYTES < got["peak"] <= 15.5e9
    with open(os.path.join(aot_v5e.REPO, "benchmark", "configs", XING4 + ".json")) as fh:
        recorded = json.load(fh)["memory_analysis_v5e_bytes"]
    assert got["argument"] == recorded["arguments"] and got["peak"] <= recorded["peak_memory"]
    assert got["peak"] <= 15.01e9
    assert got["remat_products"] <= 1 and len(got["remat_clones"]) <= 16


def test_a_layer_saves_the_streams_once_and_attentions_operands_at_their_own_widths(aot):
    """What the backward layer loop is handed a layer at a time: the four streams in bf16 once, q and k at 192, v and
    o at 128 (one matrix's two widths: no padded copy), the row statistics, and the attention sublayer's H_post and
    H_res, which cross the attention call beside them; nothing of the streams in float32."""
    stacks = aot(XING4)["stacks"]
    assert stacks["bf16[4,1,4,4096,3584]"] == 1 and not [s for s in stacks if s.startswith("f32[4,1,4,4096,3584]")]
    assert stacks["bf16[4,1,32,4096,192]"] == 2 and stacks["bf16[4,1,32,4096,128]"] == 2
    assert stacks["f32[4,32,4096,1]"] == 1 and stacks["f32[4,4,1,4096]"] == 1 and stacks["f32[4,4,4,1,4096]"] == 1
    assert aot(XING4)["stacked_bytes"] < 3.6e9


def test_nothing_of_the_logits_size_stands_beside_the_logits(aot):
    """4,096 x 16,384: until PR 68 d logits, their relayout, the scatter's `f32[67108864]`, the bf16 copy and the
    head's clone `fusion.2183.remat` beside the head's `fusion.2183`."""
    aot_v5e.holds_the_logits_alone(aot(XING4))


def test_no_pass_rounds_an_expert_matrix_outside_the_optimizer(aot):
    aot_v5e.rounds_the_experts_matrices_in_the_optimizer_alone(aot(XING4), 0)
