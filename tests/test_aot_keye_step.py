"""The Keye cell's whole train step, compiled ahead of time for a `v5e:2x2`
(`tests/aot_v5e.py`, a process of its own; until PR 46 in `tests/test_model_scopes.py`)."""

import json
import os

import pytest

import aot_v5e
from aot_v5e import REPO
from benchmark.harness.program_trace import PHASES, phase

# The Keye cell's step (PR 42): what its five layers, one scan, hand to Mosaic. The two flash kernels
# a (Q tile, K tile) pair a program over 272 of 512 pairs of 512 x 1,024, the selection kernel and the
# indexer loss's (forward only: its gradients are made there), and the held-prefix expert layer's.
KEYE = "keye-vl-2.0-30b-a3b-ep8"
KEYE_KERNELS = {"flash_fwd": 1, "flash_bwd": 1, "select": 1, "index_loss": 1}


@pytest.fixture(scope="module")
def aot():
    return aot_v5e.steps(KEYE)


@pytest.mark.parametrize("kernel", sorted(KEYE_KERNELS))
def test_the_keye_step_hands_mosaic_the_selection_the_streamed_kernels_and_the_loss(aot, kernel):
    """Every kernel of `ops/lightning_indexer.py` and both flash kernels once in the scanned layer,
    under the scope their reader looks for, in the phase they belong to; the step's temporaries beside
    6.75 GB of arguments fit the chip (the recorded `memory_analysis_v5e_bytes` are this compile's)."""
    got = aot(KEYE)
    scopes = [n for n in got["mosaic_scopes"] if n.split("/")[-2] == kernel]
    assert len(scopes) == KEYE_KERNELS[kernel], got["mosaic_scopes"]
    (scope,) = scopes
    assert phase(scope) == ("backward" if kernel == "flash_bwd" else "forward")
    assert "attention" in scope.split("/") and "rematted_computation" not in scope.split("/")
    if kernel.startswith("flash_"):
        assert "tiles_272of512" in scope.split("/") and "keys_2112of2176" in scope.split("/")
        # The forward's program is a pair of a key/value head's whole group of 8 (PR 44); the backward's a head's.
        assert ("group_8" in scope.split("/")) == (kernel == "flash_fwd")
    else:
        assert scope.split("/").count(kernel) == 2  # the scope the `dsa.*_ms` readers pick, and the kernel's name
    with open(os.path.join(REPO, "benchmark", "configs", KEYE + ".json")) as fh:
        recorded = json.load(fh)["memory_analysis_v5e_bytes"]
    # (what the file records, and the held experts' bf16 copy beside it since PR 64: 754,974,720 B)
    assert got["argument"] - got["compute_copy_bytes"] == recorded["arguments"] and got["temp"] <= recorded["temporaries"]
    assert got["phases"] == sorted(PHASES)


def test_the_keye_step_holds_no_clone_of_a_product_and_its_scan_stacks_o_once(aot):
    """What XLA's own rematerialization makes a second time (`aot_v5e.remat_products`): nothing. Until PR 60 it
    cloned q's and k's projections and `W_o`'s (`fusion.727.remat`, `.733.remat`, `.716.remat`: 10,083 instructions,
    a peak of 14,886,214,656 B, temporaries 12,938,390,016) to fit a backward loop that was handed o twice: the
    flash kernel's own `bf16[5,32,16384,128]` beside the `bf16[5,1,32,16384,128]` that `out_part`'s checkpoint
    saves, 640 MiB of one value. The loop carries q and o now, each once and in the caller's shape."""
    got = aot(KEYE)
    assert got["remat_products"] == 0
    assert aot_v5e.stacks_ending(got, ",32,16384,128]") == {"bf16[5,1,32,16384,128]": 2}, got["stacks"]
    assert aot_v5e.stacks_ending(got, ",4,16384,128]") == {"bf16[5,1,4,16384,128]": 2}  # k and v on their own four heads
    # PR 64: the loop is handed the experts' matrices as their bf16 copies and stacks their gradient in bf16 (three
    # `f32[5,16,...]` of its operands are bf16 now): - 754,974,720 B, which is the copy's own size, and the peak falls
    # from 14,620,269,568 to 13,726,358,016 with the hoisted cast that is no temporary any more.
    assert got["stacked_bytes"] == 4_338_005_760  # 5,092,980,480 until PR 64; 5,764,069,120 with the second o
    assert aot_v5e.stacks_ending(got, ",16,768,2048]") == {"bf16[5,16,768,2048]": 2}, got["stacks"]
    assert aot_v5e.stacks_ending(got, ",16,2048,768]") == {"bf16[5,16,2048,768]": 4}
    assert got["peak"] <= 13_726_358_016 + (8 << 20) and got["recomputed"] <= 480


def test_xla_gathers_the_cotangent_of_the_held_prefix_out_of_hbm(aot):
    """What stands today (PR 72), pinned so that a libtpu that flips it is seen; the open item of PERF.md section 7,
    not a wish: at 16,384 x 2,048 (64 MiB, half of the v5e's VMEM) and 32,768 rows asked, the layer `while`'s body
    gathers the tokens out of a source marked `S(1)`, forward and again for the backward pass, and the cotangent
    (`combine`'s transpose) out of HBM, at a copy descriptor's price a row: about 3.5 ms a step over the five
    layers by SmallThinker's prices (not measured here). `moe._rows_by` keeps XLA's gather at this size because
    Trinity-Mini's cotangent of the same size lies in VMEM (`tests/test_aot_trinity_step.py`)."""
    got = aot(KEYE)
    assert aot_v5e.prefix_form_calls(got) == ([], 0)
    assert aot_v5e.rows_gathered_by_xla(got) == {
        ("forward", "dispatch"): [True], ("backward", "dispatch"): [True], ("backward", "combine"): [False]}
    assert {(g["rows"], g["source_rows"]) for g in got["row_gathers"]} == {(32768, 16384), (131072, 16384)}


def test_nothing_of_the_logits_size_stands_beside_the_logits(aot):
    """16,384 x 18,992: until PR 68 `fusion.435`, `copy.578` (6.49 ms a step on the chip: PERF.md section 5, PR 53),
    `fusion.11` and `reshape.2166` beside the head's product; 9,932 instructions for 10,028, the peak the same."""
    aot_v5e.holds_the_logits_alone(aot(KEYE))


def test_no_pass_rounds_an_expert_matrix_outside_the_optimizer(aot):
    """The parent's step cast the three stacked matrices once, hoisted out of the layer loop."""
    aot_v5e.rounds_the_experts_matrices_in_the_optimizer_alone(aot(KEYE), 3)
