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
    """551.0 M parameters x 12 B of arguments and the 377.5 M held expert parameters' bf16 copy (PR 64; the
    gradient is a temporary), and XLA's peak under the chip's 16.91 GB, under the Keye cell's too: the same positions a
    layer with no selection and no indexer's residuals. Since PR 64 under the parent's 14,064,650,240 by 0.9 GB."""
    got = aot(SDAR)
    assert got["compute_copy_bytes"] == 377_487_360 * 2
    state = got["argument"] - got["compute_copy_bytes"]
    assert 0 <= state - 550_984_960 * 12 < 1 << 20  # the state; beside it the step, the counts, the batch, padding
    assert got["peak"] is None or got["peak"] <= 13_153_961_472 + (8 << 20)
    assert got["phases"] == sorted(PHASES)
    moe = sorted({n.split("/")[-2] for n in got["mosaic_scopes"]} - {"flash_fwd", "flash_bwd"})
    assert moe == ["gmm_dlhs", "gmm_drhs", "gmm_fwd", "sum_rows"]  # (XLA's gather out of 16,384 rows: `moe._rows_by`)


def test_the_sdar_step_holds_no_clone_of_a_product_and_its_scan_stacks_o_once(aot):
    """As the Keye step (`tests/test_aot_keye_step.py`): until PR 60 the backward loop was handed o twice (the flash
    kernel's `bf16[5,32,16384,128]` beside `out_part`'s `bf16[5,1,32,16384,128]`) and XLA's rematerialization made
    q's projection again to fit it (`fusion.618.remat`; 10,230 instructions, a peak of 14,565,869,568 B)."""
    got = aot(SDAR)
    assert got["remat_products"] == 0
    assert aot_v5e.stacks_ending(got, ",32,16384,128]") == {"bf16[5,1,32,16384,128]": 2}, got["stacks"]
    # PR 64: the experts' stacked gradient is bf16 like the copies the loop is handed: - 754,974,720 B.
    assert got["stacked_bytes"] == 3_940_198_400  # 4,695,173,120 until PR 64; 5,366,261,760 with the second o
    assert aot_v5e.stacks_ending(got, ",16,768,2048]") == {"bf16[5,16,768,2048]": 2}, got["stacks"]
    assert aot_v5e.stacks_ending(got, ",16,2048,768]") == {"bf16[5,16,2048,768]": 4}
    assert got["recomputed"] <= 407


def test_xla_gathers_the_cotangent_of_the_held_prefix_out_of_hbm(aot):
    """What stands today (PR 72), pinned so that a libtpu that flips it is seen; the open item of PERF.md section 7,
    not a wish: at 16,384 x 2,048 (64 MiB, half of the v5e's VMEM) and 32,768 rows asked, the layer `while`'s body
    gathers the tokens out of a source marked `S(1)`, forward and again for the backward pass, and the cotangent
    (`combine`'s transpose) out of HBM, at a copy descriptor's price a row: about 3.5 ms a step over the five
    layers by SmallThinker's prices (not measured here). `moe._rows_by` keeps XLA's gather at this size because
    Trinity-Mini's cotangent of the same size lies in VMEM (`tests/test_aot_trinity_step.py`)."""
    got = aot(SDAR)
    assert aot_v5e.prefix_form_calls(got) == ([], 0)
    assert aot_v5e.rows_gathered_by_xla(got) == {
        ("forward", "dispatch"): [True], ("backward", "dispatch"): [True], ("backward", "combine"): [False]}
    assert {(g["rows"], g["source_rows"]) for g in got["row_gathers"]} == {(32768, 16384), (131072, 16384)}


def test_nothing_of_the_logits_size_stands_beside_the_logits(aot):
    """8,192 noised positions x 18,992 through `causal_lm_loss(weights=)`: until PR 68 `fusion.322`, `copy.524`,
    `fusion.9` and `reshape.1703` beside the head's product; 10,114 instructions for 10,180, the peak the same."""
    aot_v5e.holds_the_logits_alone(aot(SDAR))


def test_no_pass_rounds_an_expert_matrix_outside_the_optimizer(aot):
    """The parent's step cast the three stacked matrices once, hoisted out of the layer loop."""
    aot_v5e.rounds_the_experts_matrices_in_the_optimizer_alone(aot(SDAR), 3)
