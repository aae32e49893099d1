"""The Trinity cell's kernels under the window and its whole train step, compiled ahead of time for a `v5e:2x2`
(`tests/aot_v5e.py`, a process a list), beside `tests/test_aot_keye_step.py`."""

import json
import os

import pytest

import aot_v5e
from benchmark.harness.program_trace import PHASES, phase

TRINITY = "trinity-mini-ep16-l5"
V5E_HBM_BYTES = 16_909_336_064
PARAMETERS = 504_147_712
HELD_EXPERT_PARAMETERS = 201_326_592


@pytest.fixture(scope="module")
def aot():
    return aot_v5e.steps(TRINITY)


def test_the_step_hands_mosaic_four_band_calls_and_one_triangle_each_pass(aot):
    """A dense window layer and one period in one scan: both flash kernels once a layer, under the stack's scope
    `attention` and the kind's own, the window kinds' walking 90 of 512 tile pairs and scoring 600 of 720 key blocks,
    the full kind's the triangle's 272 and 2,112 of 2,176; never again in the backward pass (`save_attn`)."""
    got = aot(TRINITY)
    flash = [n.split("/") for n in got["mosaic_scopes"] if n.split("/")[-2] in ("flash_fwd", "flash_bwd")]
    by = lambda kernel, kind: [p for p in flash if p[-2] == kernel and kind in p]  # noqa: E731
    for kernel in ("flash_fwd", "flash_bwd"):
        assert (len(by(kernel, "dense_window")), len(by(kernel, "window")), len(by(kernel, "full"))) == (1, 3, 1)
        for parts in by(kernel, "window") + by(kernel, "dense_window"):
            assert "tiles_90of512" in parts and "keys_600of720" in parts and "attention" in parts
        (parts,) = by(kernel, "full")
        assert "tiles_272of512" in parts and "keys_2112of2176" in parts and "attention" in parts
    for parts in flash:
        assert phase("/".join(parts)) == ("backward" if parts[-2] == "flash_bwd" else "forward")
        assert "rematted_computation" not in parts and ("group_8" in parts) == (parts[-2] == "flash_fwd")
    # The expert layers run the grouped-matmul kernels over the held prefix in both forms of the layer.
    assert {"gmm_fwd", "gmm_dlhs", "gmm_drhs", "sum_rows"} <= {n.split("/")[-2] for n in got["mosaic_scopes"]}
    assert got["phases"] == sorted(PHASES)
    assert got["element_moves"]["scalars"] == [] and got["backward_scatter_adds"] == []


def test_xla_holds_both_gathered_sources_of_the_held_prefix_in_vmem(aot):
    """What stands today (PR 72): at 16,384 x 2,048 (64 MiB, half of the v5e's VMEM) and 16,384 rows asked, the tokens'
    gathers and the cotangent's all read a source marked `S(1)`, so `moe._rows_by` keeps XLA's gather for all twelve:
    the kernel would cost this cell about 0.5 ms a step. SDAR's and Keye's cotangent of the same size lies in HBM
    (`tests/test_aot_sdar_step.py`): no shape tells them apart (PERF.md section 7)."""
    got = aot(TRINITY)
    assert aot_v5e.prefix_form_calls(got) == ([], 0)
    assert aot_v5e.rows_gathered_by_xla(got) == {
        ("forward", "dispatch"): [True] * 4, ("backward", "dispatch"): [True] * 4, ("backward", "combine"): [True] * 4}
    assert {(g["rows"], g["source_rows"]) for g in got["row_gathers"]} == {(16384, 16384), (131072, 16384)}


def test_the_step_fits_the_chip_with_a_gigabyte_and_four_tenths_to_spare(aot):
    """504.1 M parameters x 12 B and the 201.3 M held expert parameters' bf16 copy (PR 64) are the arguments (the
    gradient is a temporary), and what the file records is this compile's less the copy. Pinned again at PR 64, on
    purpose: the step is unrolled, the copy's 402,653,184 B come back only as the casts that are no temporaries any
    more, and XLA's peak is 15,503,016,448 where the parent's was 15,301,689,856: 3 MB over ISSUE 61's 15.5 GB
    of the chip's 16.91, with one more clone of a product (the dense layer's second `bsd,df->bsf`,
    `fusion.1276.remat`: `remat_products` 7 for 6; PERF.md section 6, PR 64). Pinned again at PR 68, on purpose: the
    loss picks the target's logit by a compare and a sum, so the four arrays of the logits' size that its gather's
    gradient cost (1.64 GB each in float32) are gone: 14,620,607,488 B (86.5 % of the chip), 30,471 instructions
    for 30,567, temporaries 8,684,443,648 for 9,132,546,560, the same seven clones."""
    got = aot(TRINITY)
    assert got["compute_copy_bytes"] == HELD_EXPERT_PARAMETERS * 2
    state = got["argument"] - got["compute_copy_bytes"]
    assert 0 <= state - PARAMETERS * 12 < 16 << 20  # beside the state: step, counts, the batch
    assert got["peak"] is not None and 0.25 * V5E_HBM_BYTES < got["peak"] <= 14.63e9
    with open(os.path.join(aot_v5e.REPO, "benchmark", "configs", TRINITY + ".json")) as fh:
        recorded = json.load(fh)["memory_analysis_v5e_bytes"]
    assert state == recorded["arguments"] and got["peak"] - got["compute_copy_bytes"] <= recorded["peak_memory"]
    assert got["remat_products"] <= 7 and len(got["remat_clones"]) <= 13 and got["recomputed"] <= 199


def test_nothing_of_the_logits_size_stands_beside_the_logits(aot):
    """16,384 x 25,024: until PR 68 `fusion.451`, `copy.1683` (4.96 ms a step on the chip: ledger, PR 67), `fusion.27`
    and `reshape.5686` beside the head's `fusion.2238`."""
    aot_v5e.holds_the_logits_alone(aot(TRINITY))


def test_no_pass_rounds_an_expert_matrix_outside_the_optimizer(aot):
    """The parent's step cast each of the twelve matrices forward and again backward, in both forms of a layer."""
    aot_v5e.rounds_the_experts_matrices_in_the_optimizer_alone(aot(TRINITY), 48)
