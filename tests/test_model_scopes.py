"""The train step names its own work, and the names cost nothing.

`jax.named_scope`s in `models/stack.py` (for every model: `blocks`, `qkv`,
`attention`, `head`, `loss`), in each model's own parts, in `training.py`, and
`name=` on the `pl.pallas_call`s end up in every instruction's `op_name` in the
compiled program. Here: the CPU compile of each model's nano step puts what
carries a name into the four named phases, and the ahead-of-time `v5e:2x2`
compile of the benchmark's two dense configurations is the pinned program:
same instruction count, same `memory_analysis()`, the same Mosaic calls, every
block weight gathered over ICI in dense tiles. (The expert cells' steps are
`tests/test_aot_expert_steps.py`'s, the Keye cell's `tests/test_aot_keye_step.py`'s:
a file compiles two steps at most, each in a process of its own when a test
first reads it, through `tests/aot_v5e.py`.)

What reads the names is `benchmark/harness/program_trace.py`; its `phase`
rules are used here, so the model's names and their reader cannot drift.
"""

import contextlib
import os
import re
import sys

import pytest

import aot_v5e
from aot_v5e import INSTRUCTION

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.harness.program_trace import PHASES, phase, scope_map  # noqa: E402

# Every model's step carries the names `stack.py` and `training.py` open, and beside them those of
# its own parts. HALVES are the parts of a block on either side of `attention`, with what they hold.
SHARED = ("embed", "blocks", "qkv", "attention", "head", "loss", "optimizer")
OWN = {"gpt": ("out_mlp",), "llama": ("out_mlp",),
       "olmoe": ("attn_out", "moe", "router", "dispatch", "experts", "combine"),
       "lfm2": ("attn_out", "moe", "router", "dispatch", "experts", "combine",
                "short_conv", "conv_mix", "dense_mlp"),
       # PR 39: latent attention, a shared expert, and the prediction module's scope round a block,
       # a head and a loss of its own (`jvp(mtp)` in a compiled step: the parts are split on brackets).
       "glm4": ("attn_out", "moe", "router", "dispatch", "experts", "combine",
                "mla_latent", "shared_expert", "dense_mlp", "mtp"),
       # PR 42: the indexer's projections in `qkv`, the selection and the indexer's loss in `attention`.
       "keye": ("attn_out", "moe", "router", "dispatch", "experts", "combine",
                "indexer", "select", "index_loss")}
# For lfm2 also what a block with no attention in its middle holds: all of it is recomputed (off the TPU; in a step
# compiled for one, `conv_mix`'s chain is no one's residual and is not made again: `tests/test_aot_expert_steps.py`, PR 62).
HALVES = {"gpt": {"qkv", "out_mlp"}, "llama": {"qkv", "out_mlp"},
          "olmoe": {"qkv", "attn_out", "moe", "router", "experts"},
          "lfm2": {"qkv", "attn_out", "moe", "router", "experts", "short_conv", "conv_mix", "dense_mlp"},
          "glm4": {"qkv", "attn_out", "moe", "router", "experts", "mla_latent", "shared_expert", "dense_mlp"},
          # The indexer's projections are made again with `qkv`; the selection and the loss stand where
          # the attention call stands and are kept with it.
          "keye": {"qkv", "attn_out", "moe", "router", "experts", "indexer"}}
SCOPES = SHARED + OWN["gpt"] + ("grad_norm",)
# This tree's programs (ahead-of-time compile for v5e:2x2 on this installation,
# pinned at PR 30, which stored the attention weights as matrices): instructions
# of the compiled text and `memory_analysis()`. A PR that means to change
# neither sees it here. `benchmark/configs/*.json` still record PR 22's
# `memory_analysis_v5e_bytes` (3,150 / 3,673 instructions): they are the
# benchmark's files and say what that PR sized the cells by.
# Pinned again at PR 60, on purpose: the flash kernels keep the caller's own arrays, so the backward layer loop is
# handed four stacks of a head's size (q, k, v, o) and not five (o again under the kernels' own (batch * heads,
# seq, d)). Until then 3,174 instructions / 9,234,833,920 B of temporaries (a peak of 10,620,071,936 B; now
# 10,234,195,968) and, on four chips, 3,710 / 9,007,949,312 (11,844,459,520; now 10,586,168,320).
# Pinned again at PR 68, on purpose: the loss picks the target's logit by a compare and a sum where it gathered it, so
# the gather, its gradient's scatter and what fed them are fewer and smaller fusions: 3,096 -> 2,995 instructions and
# - 258,048 B of temporaries, on four chips 3,686 -> 3,590 and - 64,512 B; both peaks to the byte (several rows a
# device: XLA never made the scatter's logits-sized arrays here, `logits_sized` reads the logits alone on both sides).
PARENT = {
    "gpt2-medium": {"instructions": 2995, "argument": 4259378176, "temp": 8461307904,
                    "output": 4259343360, "alias": 4259341312},
    "gpt2-xl-fsdp4": {"instructions": 3590, "argument": 4714580992, "temp": 7749494784,
                      "output": 4714564608, "alias": 4714562560},
}
# The residuals of a head's size that each cell's backward layer loop carries (`aot_v5e.layer_stacks`): q, k, v and
# o, in the caller's (batch, heads, seq, d) under the layers; inside the four-chip step's `shard_map` too.
HEAD_STACKS = {"gpt2-medium": "bf16[24,8,16,1024,64]", "gpt2-xl-fsdp4": "bf16[48,4,25,1024,64]"}
# What each cell's step hands to Mosaic: the tile schedule its two flash kernels run under
# (head_dim 64 at 1,024 positions).
KERNELS = {
    "gpt2-medium": {"tiles": "tiles_3of4", "moe": {}},
    "gpt2-xl-fsdp4": {"tiles": "tiles_3of4", "moe": {}},
}
# Temporaries of the steps before PR 30. gpt2-xl-fsdp4 must stay under its own
# (a cold run peaks 219 MiB from the chip's limit: PERF.md section 7); the
# one-chip step came out 999,936 bytes (0.011 %) over, in XLA's packing of the
# same buffers, and is held to that.
TEMP_BEFORE_PR30 = {"gpt2-medium": 9233833984 + 999936, "gpt2-xl-fsdp4": 9615279616}


def _nano_step(model, remat_policy):
    import jax
    import jax.numpy as jnp

    from ray_tpu import models
    from ray_tpu.models.keye_vl2 import KeyeVL2Config

    config = {"gpt": models.GPTConfig, "llama": models.LlamaConfig, "olmoe": models.OLMoEConfig,
              "lfm2": models.LFM2Config, "glm4": models.GLM4MoELiteConfig, "keye": KeyeVL2Config}
    cfg = config[model].nano(remat=remat_policy != "off",
                             remat_policy=None if remat_policy == "off" else remat_policy)
    opt = models.default_optimizer()
    state = jax.eval_shape(lambda: models.create_train_state(cfg, jax.random.PRNGKey(0), opt))
    batch = {"tokens": jax.ShapeDtypeStruct((4, 65), jnp.int32)}
    return models.make_train_step(cfg, opt).lower(state, batch).compile()


@pytest.mark.parametrize("model, remat_policy", [
    ("gpt", "save_attn"), ("gpt", "dots"), ("gpt", "off"),
    # `llama.py` named nothing before PR 31: its step fell into no phase.
    ("llama", "save_attn"), ("olmoe", "save_attn"),
    # A patterned stack (PR 35): leading layers, a scan over periods, layers with no attention.
    ("lfm2", "save_attn"), ("lfm2", "off"),
    # Latent attention, a shared expert and a second prediction depth (PR 39).
    ("glm4", "save_attn"), ("glm4", "off"),
    # A layer that brings its own `attend` (PR 42): the selection and the indexer's loss stay out of the remat.
    ("keye", "save_attn"), ("keye", "off")])
def test_what_the_nano_step_names_falls_into_the_phases(model, remat_policy):
    """Of the compiled instructions that carry an `op_name` (on the CPU four
    in ten carry none: converts, constants and fusions the compiler made),
    under 5 % are in no named phase, every scope of the model shows (the
    block's, and for OLMoE the expert layer's from `models/moe.py`), and
    recompute exists exactly where `jax.checkpoint` does."""
    scopes = scope_map(_nano_step(model, remat_policy).as_text())
    assert len(scopes) > 1000
    count = {p: 0 for p in PHASES}
    for op_name in scopes.values():
        count[phase(op_name)] += 1
    assert count["other"] < 0.05 * len(scopes), count
    assert min(count[p] for p in ("forward", "backward", "optimizer")) > 100, count
    assert (count["recompute"] > 100) == (remat_policy != "off"), count
    parts = {part for op_name in scopes.values() for part in re.split(r"[/()]", op_name)}
    # `grad_norm` computes what `clip_by_global_norm` already did under
    # `optimizer`: XLA keeps one of the two, so either name may be all that is left.
    assert set(SHARED + OWN[model]) <= parts, set(SHARED + OWN[model]) - parts
    # What runs again keeps the name of the part it belongs to, under the
    # region's: which parts are recomputed is the policy's to say. Under
    # `save_attn` attention stays out of it; the rest of the block is in it.
    # (Off the TPU the indexer's loss makes each chunk of queries again on its own, whatever the policy:
    # `ops/lightning_indexer.py _xla_index_loss`. The kernel that stands there on the chip does not.)
    again = [n for n in scopes.values() if "rematted_computation" in n.split("/") and "index_loss" not in n.split("/")]
    inside = {part for n in again for part in n.split("/")}
    want = {"save_attn": HALVES[model], "dots": HALVES[model] | {"attention"}, "off": set()}
    assert inside & (HALVES[model] | {"attention"}) == want[remat_policy]
    assert not inside & {"select"} or remat_policy != "save_attn"


def test_names_change_no_instruction_and_no_byte_of_the_nano_step(monkeypatch):
    import jax
    from jax.experimental import pallas as pl

    named = _nano_step("gpt", "save_attn")
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    real = pl.pallas_call
    monkeypatch.setattr(pl, "pallas_call", lambda *a, name=None, **kw: real(*a, **kw))
    bare = _nano_step("gpt", "save_attn")
    assert not set(SCOPES) & {p for n in scope_map(bare.as_text()).values() for p in n.split("/")}
    assert len(INSTRUCTION.findall(named.as_text())) == len(INSTRUCTION.findall(bare.as_text()))
    a, b = named.memory_analysis(), bare.memory_analysis()
    for key in ("argument_size_in_bytes", "temp_size_in_bytes", "output_size_in_bytes",
                "alias_size_in_bytes"):
        assert getattr(a, key) == getattr(b, key), key


# ------------------------------------------------- ahead of time, for the v5e
@pytest.fixture(scope="module")
def aot():
    return aot_v5e.steps(*PARENT)


@pytest.mark.parametrize("cell", sorted(PARENT))
def test_the_v5e_program_is_the_pinned_one_and_needs_no_more_memory(aot, cell):
    aot_v5e.is_the_pinned_program(aot(cell), cell, PARENT[cell], TEMP_BEFORE_PR30.get(cell, PARENT[cell]["temp"]))


@pytest.mark.parametrize("cell", sorted(PARENT))
def test_the_backward_loop_carries_four_stacks_of_a_heads_size_and_xla_clones_no_product(aot, cell):
    """384 MiB each on `gpt2-medium` (five until PR 60: `bf16[24,128,1024,64]` four times and `bf16[24,8,16,1024,64]`
    once), 600 MiB on four chips (`bf16[48,100,1024,64]` four times and `bf16[48,4,25,1024,64]` once)."""
    got = aot(cell)
    assert aot_v5e.stacks_ending(got, ",1024,64]") == {HEAD_STACKS[cell]: 4}, got["stacks"]
    assert got["remat_products"] == 0


def test_every_block_weight_crosses_ici_in_dense_tiles(aot):
    """gpt2-xl-fsdp4 gathers four weights a layer in the forward body and four
    in the backward body (the MLP's in several asynchronous parts). Each has a
    minor dimension that fills the 128 lanes of a tile; before PR 30 `qkv_w`
    and `out_w` were gathered with head_dim = 64 there, half of every tile
    padding. The four relayout copies a layer that followed those gathers are
    still there (PERF.md, PR 30, says what they cost): the count is pinned so
    that the PR that removes them, or adds one, says so."""
    got = aot("gpt2-xl-fsdp4")
    assert min(got["gather_minor_dims"]) >= 128, got["gather_minor_dims"]
    assert set(got["gather_minor_dims"]) == {1600, 4800, 6400}  # out_w, qkv_w, the MLP's two
    assert got["gathered_weight_copies"] == 4
    assert aot("gpt2-medium")["gather_minor_dims"] == []  # one chip: nothing to gather


@pytest.mark.parametrize("cell", sorted(PARENT))
def test_one_kernel_under_flash_fwd_one_under_flash_bwd_and_all_phases(aot, cell):
    aot_v5e.has_one_flash_kernel_a_pass_and_all_phases(aot(cell), KERNELS[cell])
