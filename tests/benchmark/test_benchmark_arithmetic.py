"""The FLOPs and bytes the benchmark divides by, against hand-worked numbers."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness.manifest import Manifest  # noqa: E402
from benchmark.harness.peaks import peaks_for  # noqa: E402
from benchmark.models import gpt2  # noqa: E402

# By hand. medium: 24 layers x 12 x 1024^2 = 301,989,888, head 50,304 x 1024 =
# 51,511,296. xl: 48 x 12 x 1600^2 = 1,474,560,000, head 50,304 x 1600 =
# 80,486,400. Attention, full square: 12 x layers x width x 1024.
HAND = {
    "gpt2-medium": (353_501_184, 6 * 353_501_184 + 301_989_888),
    "gpt2-xl-fsdp4": (1_555_046_400, 6 * 1_555_046_400 + 943_718_400),
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_train_flops_per_token(name):
    c = Manifest().config(name)
    params, flops = HAND[name]
    assert gpt2.matmul_params(c) == params
    assert gpt2.train_flops_per_token(c, 1024) == float(flops)
    assert flops in (2_422_996_992, 10_273_996_800)


def test_the_programs_own_count_is_within_one_percent():
    from ray_tpu.models import GPTConfig, train_flops_per_token

    c = Manifest().config("gpt2-medium")
    theirs = train_flops_per_token(GPTConfig.gpt2_medium(), 1024)
    ours = gpt2.train_flops_per_token(c, 1024)
    assert ours < theirs < 1.01 * ours  # theirs counts wpe, biases and LayerNorm


@pytest.mark.parametrize("name,rows,flops,nbytes", [
    # per (row, head): 6 products x 2 x 1024^2 x 64 / 2 = 402,653,184 FLOPs;
    # bytes (4 x 131,072 + 4,096) + (7 x 131,072 + 2 x 4,096) = 1,454,080.
    ("gpt2-medium", 8, 402_653_184 * 8 * 16 * 24, 1_454_080 * 8 * 16 * 24),
    ("gpt2-xl-fsdp4", 4, 402_653_184 * 4 * 25 * 48, 1_454_080 * 4 * 25 * 48),
])
def test_flash_flops_and_bytes_per_step(name, rows, flops, nbytes):
    c = Manifest().config(name)
    assert c["batch"]["global_rows"] // (c["layout"]["num_workers"]) == rows
    assert gpt2.flash_flops_per_step(c, rows, 1024) == float(flops)
    assert gpt2.flash_bytes_per_step(c, rows, 1024) == float(nbytes)


def test_the_kernels_floor_is_compute_at_these_shapes():
    v5e = peaks_for("TPU v5 lite")
    c = Manifest().config("gpt2-medium")
    compute = gpt2.flash_flops_per_step(c, 8, 1024) / v5e["bf16_flops_per_s"]
    memory = gpt2.flash_bytes_per_step(c, 8, 1024) / v5e["hbm_bytes_per_s"]
    assert compute == pytest.approx(6.279e-3, rel=1e-3)  # 1.237 TFLOP / 197 TFLOP/s
    assert memory == pytest.approx(5.454e-3, rel=1e-3)   # 4.467 GB / 819 GB/s
    assert compute > memory
