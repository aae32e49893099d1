"""The Olmo-Hybrid cell's kernels and its whole train step, compiled ahead of time for a `v5e:2x2`
(`tests/aot_v5e.py`, a process a list)."""

import json
import os
import re

import pytest

import aot_v5e
from benchmark.harness.program_trace import PHASES, phase

# The cell's step (PR 51): two periods of (linear, linear, linear, full) in one scan under `fsdp=4`, a row a chip.
# A linear layer's scan is two Mosaic calls inside a shard_map (XLA cannot partition one), the full layer's the two
# flash kernels at OLMoE's head (30 heads of 4,096 x 128). Since PR 54 the gradient of a linear layer's short
# convolutions (q, k, v) is a Mosaic call each, `short_conv_bwd`, inside a shard_map of its own.
OLMO_HYBRID = "olmo-hybrid-7b-fsdp4"
GDN_4K = "gdn:1x30x4096x96x192"
CONV_QK, CONV_V = "short_conv:1x4096x30x96x1", "short_conv:1x4096x30x192x0"
V5E_HBM_BYTES = 16_909_336_064
PARAMETERS = 2_435_748_072


@pytest.fixture(scope="module")
def aot():
    return aot_v5e.Cases([GDN_4K, CONV_QK, CONV_V], ["step:" + OLMO_HYBRID])


def test_the_scans_kernels_compile_for_the_v5e_at_the_cells_widths(aot):
    """(1, 30, 4096, 96 / 192) bf16: neither width a multiple of 128 lanes, a state of 96 x 192 f32 in VMEM
    scratch carried along the sequential axis, every f32 product at full precision."""
    from ray_tpu.ops import gated_delta_rule as gdn

    got = aot[GDN_4K]
    assert got["mosaic_calls"] == 2 and got["kernels"] == ["gdn_bwd", "gdn_fwd"]
    assert got["chunks"] == [f"chunk_{gdn.CHUNK}"]
    # The plan beside it: the heads a program walks, what the rule picks for the 30 heads a device of the cell
    # holds. That many heads unrolled in one body lower for the v5e and fit its VMEM: this compile is the proof.
    heads = gdn.heads_per_program(30, 4096, gdn.CHUNK, 96, 192, 2)
    assert heads > 1 and got["plans"] == [f"heads_{heads}of30"]
    # The states the forward kernel keeps for the backward one: one a chunk a head, f32.
    assert got["states"] == [f"f32[30,{4096 // gdn.CHUNK},96,192]"]


@pytest.mark.parametrize("case", [CONV_QK, CONV_V], ids=["q_or_k", "v"])
def test_the_short_convolutions_gradient_kernel_compiles_for_the_v5e_at_the_cells_widths(aot, case):
    """(1, 4096, 2880) with the L2 norm over heads of 96 (7.5 tiles of 384 channels: the last one passes the
    array's end) and (1, 4096, 5760) in heads of 192 without it, bf16, the cotangent heads-first: a program holds a
    row's 4,096 positions of a tile, three blocks of 3-4 MiB with two buffers each, over Mosaic's default VMEM.
    Forward there is no kernel: the chain is XLA's."""
    from ray_tpu.ops import short_conv as sc

    got = aot[case]
    assert got["mosaic_calls"] == 1 and got["kernels"] == ["short_conv_bwd"]
    assert got["plans"] == [f"tile_{sc.tile_of(96)}/rows_4096"]


def test_the_short_convolutions_gradient_is_nine_calls_a_period_and_the_chain_is_not_recomputed(aot):
    """Three linear places a period x (q, k, v), all in the backward pass, each under `gdn/gdn_conv` (what
    `gdn.conv_ms` reads) in a shard_map, its scope naming the tile and the rows a program holds. The kernel keeps z
    alone of the forward pass, so the remat of the layer's first part has nothing of the chain to make again."""
    got = aot["step:" + OLMO_HYBRID]
    calls = [n.split("/") for n in got["mosaic_scopes"] if n.split("/")[-2] == "short_conv_bwd"]
    assert len(calls) == 9
    for parts in calls:
        assert parts[-4:-2] == ["tile_384", "rows_4096"] and parts[-5] == "shard_map", parts
        assert "gdn" in parts and "gdn_conv" in parts and "rematted_computation" not in parts
        assert phase("/".join(parts)) == "backward"
    assert got["conv_chain_recomputed"] == 0 and got["conv_chain_forward"] > 0


def test_the_step_runs_each_kernel_once_a_layer_and_never_again_in_the_backward_pass(aot):
    got = aot["step:" + OLMO_HYBRID]
    kernels = [(n.split("/")[-2], n) for n in got["mosaic_scopes"] if n.split("/")[-2] != "short_conv_bwd"]
    count = lambda name: sum(k == name for k, _ in kernels)  # noqa: E731
    # One period's layers are unrolled inside the scan over the two periods: three linear places, one full.
    assert (count("gdn_fwd"), count("gdn_bwd"), count("flash_fwd"), count("flash_bwd")) == (3, 3, 1, 1)
    for name, scope in kernels:
        parts = scope.split("/")
        if name.startswith("gdn_"):  # the kernels' scope names the plan, inside the step's shard_map too
            assert re.fullmatch(r"heads_\d+of30", parts[-3]) and parts[-4] == "chunk_128", scope
        assert phase(scope) == ("backward" if name.endswith("_bwd") else "forward")
        assert "rematted_computation" not in parts and "attention" in parts and "shard_map" in parts  # `save_attn`
        assert ("gdn" in parts) == name.startswith("gdn_")
    assert got["phases"] == sorted(PHASES)


def test_the_step_fits_the_chip_with_the_state_sharded_four_ways(aot):
    """2,435.7 M parameters x 12 B over four chips are the arguments (the f32 gradient is a temporary), XLA's peak
    a chip is under the chip's 16.91 GB, and the file records what this compile gave."""
    got = aot["step:" + OLMO_HYBRID]
    assert 0 <= got["argument"] - PARAMETERS * 12 // 4 < 16 << 20  # beside the state: step, counts, batch, padding
    assert got["peak"] is not None and got["peak"] < 0.95 * V5E_HBM_BYTES
    assert got["peak"] > 0.25 * V5E_HBM_BYTES  # the contract's floor for a new cell
    with open(os.path.join(aot_v5e.REPO, "benchmark", "configs", OLMO_HYBRID + ".json")) as fh:
        recorded = json.load(fh)["memory_analysis_v5e_bytes"]
    assert got["argument"] <= recorded["arguments"] and got["peak"] <= recorded["peak"] * 1.01
    # Every gathered weight comes in its stored layout: no relayout copy of a gathered matrix.
    assert got["gathered_weight_copies"] == 0
