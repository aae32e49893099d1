"""Compile for the v5e without holding one.

libtpu is installed, so jax can describe the devices of a `v5e:2x2` host under
JAX_PLATFORMS=cpu (`tests/aot_v5e.py`) and a jitted function can be lowered
and compiled for them. This is how the chip path is
checked on every PR from a sandbox with no chip: the Mosaic kernels must
compile, and the flagship step must lower on more than one device (XLA cannot
partition a Mosaic call; before the kernel ran inside a shard_map the
four-device step died here with "Mosaic kernels cannot be automatically
partitioned").

The cases, the subprocess they compile in and a time limit a case are
`tests/aot_v5e.py`'s; this file names the cases and pins what they give.
"""

import pytest

import aot_v5e

# memory_stats()["bytes_limit"] of one v5e chip (chip run, PR 21).
V5E_HBM_BYTES = 16_909_336_064
LONG_HEAD_64 = "kernel:8x32x4096x64"  # the lfm2 cell's attention layer: 8 rows, 32 heads of 4096 x 64
WIDE_HEAD_256 = "kernel:2x20x4096x256"  # the glm-4.7-flash cell's: 2 rows, 20 heads of 4096 x 256
LONGER_HEADS = ("kernel:2x32x8192x64", "kernel:1x16x8192x128")  # what failed in `flash_bwd` until PR 39
# Between the largest head the backward program holds whole (4096 x 128) and the largest the forward
# program holds (4096 x 256), and 2048 x 256, whose default tile (1024) failed in both kernels.
# The expert layers of the lfm2 and glm-4.7-flash cells: 8 x 4,096 and 2 x 4,096 tokens, 4 of 64 experts a
# token, 8 held: a prefix of 32,768 and of 8,192 sorted rows of 2,048.
ROW_MOVERS = ("row_movers:32768", "row_movers:8192")
# A conv layer of the lfm2 cell (PR 62): bcu of 8 x 4,096 x (3 x 2,048) bf16, three taps, the gradient's kernel.
GATED_CONV_4K = "gated_conv:8x4096x2048x3"
# The Keye cell's attention (PR 42): one row, 32 query heads on 4 key/value heads of 16,384 x 128, a
# packed selection; and the two kernels of `ops/lightning_indexer.py` at its indexer's 16 heads of 64.
SELECTED_16K = "selected:1x32x4x16384x128"
# Heads above 2 MiB with as many key/value heads: the pair-streamed forward takes four of them a program (PR 44);
# and a llama-like 8 on 2 heads of 2,048 x 64, which streams pairs for its shared key/value heads alone.
STREAMED_HEADS = ("kernel:1x8x16384x128", "kernel:1x8x8192x256")
GROUPED_2K = "selected:2x8x2x2048x64"
# The SDAR cell's attention (PR 47): a clean and a noised copy of 8,192 under the block-diffusion mask, blocks of 4.
MASKED_16K = "masked:1x32x4x16384x128x4"
INDEXER_16K = "indexer:1x32x4x16384x128x16x64x2048"
BETWEEN_HEADS = ("kernel:2x4x3072x256", "kernel:2x4x3584x256", "kernel:2x4x6144x128",
                 "kernel:2x4x7168x128", "kernel:2x4x2048x256")


# A process a list: the flash kernels, the expert layer's, the Keye cell's. Four of these cases are also
# inside a cell's whole step, which `tests/test_aot_expert_steps.py` and `tests/test_aot_keye_step.py`
# compile; they stay here because alone they show what the step cannot (and cost 2-13 s each):
# `LONG_HEAD_64` and `row_movers:32768` that the kernel's own compile is what passes where the LFM2 step
# does (a step that went back to XLA's gather or attention would still compile); `SELECTED_16K` the same
# call without a `keep`, the plan of the forward and its scope; `INDEXER_16K` `select`'s own results.
@pytest.fixture(scope="module")
def aot():
    return aot_v5e.Cases(
        ["kernel", LONG_HEAD_64, WIDE_HEAD_256, *LONGER_HEADS, *BETWEEN_HEADS, *STREAMED_HEADS, "lower:d4", "lower:d2t2"],
        ["held_experts", *ROW_MOVERS, GATED_CONV_4K],
        [SELECTED_16K, INDEXER_16K, GROUPED_2K, MASKED_16K])


def test_topology_is_the_v5e(aot):
    assert aot[aot_v5e.TOPOLOGY]["device_kind"] == "TPU v5 lite"


def test_flash_kernels_compile_for_v5e_at_gpt2_shapes(aot):
    assert aot["kernel"]["mosaic_calls"] == 2  # forward, fused backward


def test_flash_kernels_compile_for_v5e_at_a_head_of_4096_by_64(aot):
    """`(8, 32, 4096, 64)` bf16: 64 lanes pad to 128 in VMEM, so the head takes
    what a 4096 x 128 one does and must run the same form, 512-tiles with the
    backward pass's whole-head operands single-buffered. Counted by
    `seq * head_dim` it kept the 1024-tile double-buffered form, and `flash_bwd`
    failed here with RESOURCE_EXHAUSTED in VMEM at every tile size (PR 35)."""
    assert aot[LONG_HEAD_64]["mosaic_calls"] == 2  # forward, fused backward
    assert aot[LONG_HEAD_64]["plan"] == [512, 512, 36, 8, 64, False]


@pytest.mark.parametrize("case", [WIDE_HEAD_256, *LONGER_HEADS, *BETWEEN_HEADS])
def test_flash_kernels_compile_for_v5e_at_heads_above_that_vmem(aot, case):
    """`(2, 20, 4096, 256)` bf16, GLM-4.7-Flash's latent attention: a head takes
    2 MiB of VMEM, and the loop form's backward program (q, do and two (seq, 1)
    statistics whole, dq's output block twice, an f32 dq scratch: 18 MiB before
    a tile) failed with RESOURCE_EXHAUSTED. For every head above 1 MiB the
    backward program is one (Q tile, K tile) pair with every operand streamed,
    still one fused Mosaic call; `(2, 32, 8192, 64)` and `(1, 16, 8192, 128)`,
    PERF.md's long-context failures, take the same VMEM and compile the same
    way, as does every head between (the same model at 3,072 or 3,584
    positions; 128-wide heads of 6,144 and 7,168), and all of them walk
    512-tiles: 1024-tiles at 256 wide failed in both kernels."""
    assert aot[case]["mosaic_calls"] == 2  # forward, fused backward
    seq = int(case.split("x")[2])
    n = seq // 512
    assert aot[case]["plan"] == [512, 512, n * (n + 1) // 2, n, n * n, False]


def test_both_flash_kernels_stream_pairs_under_a_selection_at_16384_by_128(aot):
    """`(1, 32 on 4, 16384, 128)` bf16 with a packed `keep`: a head is 4 MiB, which no forward program
    holds, so `flash_fwd` runs in `flash_bwd`'s pair-streamed form, each one Mosaic call under the 16
    MiB default, at pairs of 512 x 1024 (1024-row Q tiles and 2048-key K tiles fail in `flash_bwd`: its
    f32 dq scratch alone is 8 MiB here); key/value heads unrepeated; and the same two programs without a selection."""
    got = aot[SELECTED_16K]
    assert got["mosaic_calls"] == got["mosaic_calls_without_keep"] == 2
    assert got["plan"] == [512, 1024, 272, 32, 512, False] and got["kernels"] == ["flash_bwd", "flash_fwd"]
    # The forward's program is a pair of a key/value head's whole group, at the plan's own Q tile (PR 44: 10.3 MiB by
    # the smallest limit that compiles, under the default 16 with none asked for), and its scope says so.
    assert got["forward"] == [8, 512] and got["forward_scopes"] == ["group_8"]
    # Both programs score a crossed pair over its live span (PR 48): 16 of the 32 crossed pairs hold it in half their keys.
    assert got["scored"] == ["keys_2112of2176"]


@pytest.mark.parametrize("case, plan, forward", [
    (STREAMED_HEADS[0], [512, 1024, 272, 32, 512, False], [4, 512]),
    (STREAMED_HEADS[1], [512, 512, 136, 16, 256, False], [4, 512])])
def test_the_streamed_forward_takes_four_equal_heads_a_program(aot, case, plan, forward):
    """`(1, 8, 16384, 128)` and `(1, 8, 8192, 256)` bf16, as many key/value heads as heads and no selection: the
    same forward program with a group of one, four heads and their four key/value heads a program (one head a
    program was 12 % slower on the chip than the form it replaced, four are 17 % faster: PERF.md section 6, PR 44)."""
    import importlib

    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    assert aot[case]["mosaic_calls"] == 2 and aot[case]["plan"] == plan
    _, heads, seq, d = (int(n) for n in case[len("kernel:"):].split("x"))
    assert list(fa._fwd_pairs_plan(1, heads, d, 2, fa.kernel_plan((1, heads, seq, d)))) == forward


def test_both_flash_kernels_walk_the_block_diffusion_masks_live_pairs_at_16384_by_128(aot):
    """`(1, 32 on 4, 16384, 128)` bf16 under `BlockDiffusion(8192, 4)`: the two pair-streamed programs of the Keye
    cell's call with no selection operand (no int32 words a (row, query) in the program), a crossed pair's mask
    made from iotas, shifts and compares in the kernel, over 160 of the 512 pairs of 512 x 1,024."""
    got = aot[MASKED_16K]
    assert got["mosaic_calls"] == 2 and got["kernels"] == ["flash_bwd", "flash_fwd"]
    assert got["plan"] == [512, 1024, 160, 48, 512, False] and got["scopes"] == ["tiles_160of512"]
    assert got["scored"] == ["keys_1152of1280"]  # 32 of the 48 crossed pairs hold their live span in half their keys (PR 48)
    assert got["forward"] == [8, 512] and got["words_of_a_selection"] == 0


def test_grouped_heads_of_2048_by_64_stream_pairs_with_and_without_a_selection(aot):
    """`(2, 8 on 2, 2048, 64)` bf16, what `models/llama.py` calls with `n_kv_head < n_head`: two groups of four and
    their two key/value heads a program at a head of 64 lanes (`o^T` is (64, tile_q) there)."""
    got = aot[GROUPED_2K]
    assert got["mosaic_calls"] == got["mosaic_calls_without_keep"] == 2
    assert got["plan"] == [512, 1024, 6, 4, 8, False] and got["forward"] == [8, 512]
    assert got["forward_scopes"] == ["group_8"] and got["scored"] == ["keys_40of48"]


def test_the_selection_and_the_indexer_loss_compile_for_v5e_at_the_cells_shapes(aot):
    """`select` (128 queries across the lanes, their sortable scores against 16,384 keys down in 8 MiB of VMEM,
    `kI` copied in a chunk at a time, the threshold, the packed words turned over a span; under the default
    limit with none asked for) and `index_loss` (grid (1, 2080 pairs of 256 x 256), a program a whole pair: the 32 heads' products on
    their 4 key/value heads, the 16 index scores made once and kept, 10.75 MiB of VMEM by the smallest
    limit that compiles, under the default with none asked for; the gradients leave the same call, so its
    backward pass is no kernel) at the Keye cell's indexer of 16 heads of 64, top-2,048."""
    got = aot[INDEXER_16K]
    assert got["select"] == ["select"] and got["index_loss"] == ["index_loss"]
    assert got["keep"] == [1, 16384, 512] and got["index_loss_mosaic_calls"] == 1


def test_the_plans_of_the_other_cells_shapes_are_what_they_were():
    from ray_tpu.ops.flash_attention import kernel_plan

    assert kernel_plan((8, 16, 1024, 64)) == (512, 512, 3, 2, 4, True)  # gpt2-medium
    assert kernel_plan((4, 25, 1024, 64)) == (512, 512, 3, 2, 4, True)  # gpt2-xl-fsdp4, a chip
    assert kernel_plan((2, 16, 4096, 128)) == (512, 512, 36, 8, 64, False)  # olmoe-1b-7b-l1
    assert kernel_plan((16, 32, 2048, 64)) == (512, 512, 10, 4, 16, True)  # shorter heads of 64: untouched
    assert kernel_plan((8, 32, 4096, 64)) == (512, 512, 36, 8, 64, False)  # lfm2-24b-a2b-ep8-l5
    # ... and the form of the backward program behind the plan: pairs streamed above 1 MiB a head.
    import importlib

    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    streamed = lambda b, h, s, d: fa._streamed_head(s, d, 2)  # noqa: E731
    assert not any(streamed(*shape) for shape in (
        (8, 16, 1024, 64), (4, 25, 1024, 64), (2, 16, 4096, 128), (8, 32, 4096, 64), (16, 32, 2048, 64)))
    assert all(streamed(*shape) for shape in (
        (2, 20, 4096, 256), (2, 32, 8192, 64), (1, 16, 8192, 128), (2, 20, 2560, 256), (2, 20, 3072, 256),
        (2, 16, 4608, 128), (2, 16, 7680, 128), (2, 4, 2048, 256)))


def test_a_share_of_the_experts_holds_no_array_as_long_as_every_pair_where_the_prefix_runs(aot):
    """`moe_mlp` with fewer experts than the router scores runs its sorted form
    over the prefix of the sort that the held pairs fill, or over every pair
    where they do not fit it: a `conditional` in the forward pass and one in
    the backward pass (the recomputation's has no reader and is gone). The
    `[pairs, D]` and `[pairs, F]` arrays are all inside the whole-length
    branches: the branch that runs when the router is anywhere near even
    holds none, forward or backward, and neither does the program around
    them. Both branches call the kernels."""
    got = aot["held_experts"]
    assert len(got["wide_by_branch"]) == 4 and got["wide_outside"] == 0, got
    prefix, whole = sorted(got["wide_by_branch"])[:2], sorted(got["wide_by_branch"])[2:]
    assert prefix == [0, 0] and min(whole) > 0, got
    # 2,048 tokens of 256: a source that XLA's gather serves (`moe._rows_by`), so no `gather_rows`.
    assert got["kernels"] == ["gmm_dlhs", "gmm_drhs", "gmm_fwd", "sum_rows"]


@pytest.mark.parametrize("case, rows", zip(ROW_MOVERS, (32768, 8192)))
def test_the_row_movers_of_the_prefix_form_compile_for_v5e_at_the_cells_shapes(aot, case, rows):
    """`gather_rows` (tokens into expert order, written a block of tokens at a
    time, at 128-row chunks) and `sum_rows` at the 256-row chunk that a layer
    holding an eighth of the experts gets (`chunk_rows`; 512 where every pair
    is held): one Mosaic call each, at the prefix lengths the LFM2 and
    GLM-4.7-Flash steps run (the second's gather stays XLA's in the step,
    `moe._rows_by`: the kernel compiles there all the same)."""
    got = aot[case]
    assert {k: got[k] for k in got if k != "seconds"} == {
        "rows": rows, "chunk_rows": [256, 128], "kernels": ["gather_rows", "sum_rows"]}


def test_the_gated_short_convolutions_gradient_kernel_compiles_for_v5e_at_the_cells_shape(aot):
    """(8, 4096, 6144) bf16 and three taps: a program holds a row's 4,096 positions of a 512-channel tile of b, c, u
    and the cotangent and of db, dc, du, seven blocks of 4 MiB with two buffers each (56 of the 64 MiB the kernel
    allows itself, over Mosaic's default), and copies its three results into the one `dbcu` itself. Forward
    there is no kernel: the chain is XLA's."""
    from ray_tpu.ops import short_conv as sc

    got = aot[GATED_CONV_4K]
    assert got["mosaic_calls"] == 1 and got["kernels"] == ["gated_conv_bwd"]
    assert got["plans"] == [f"tile_{sc.gated_tile(4096, 2048, 2)}/rows_4096"] == ["tile_512/rows_4096"]


@pytest.mark.parametrize("mesh", ["d4", "d2t2"])
def test_gpt2_small_step_lowers_on_four_chips_with_the_kernel(aot, mesh):
    assert aot[f"lower:{mesh}"]["mosaic_calls"] == 2


@pytest.mark.slow
def test_gpt2_small_step_compiles_and_fits_hbm_on_one_and_four_chips():
    cases = ["compile:d1", "compile:d4", "compile:d2t2"]
    out = aot_v5e.Cases(cases)
    for case in cases:
        r = out[case]
        assert r["mosaic_calls_compiled"] == 2, (case, r)
        assert 0 < r["device_bytes"] < V5E_HBM_BYTES, (case, r)

