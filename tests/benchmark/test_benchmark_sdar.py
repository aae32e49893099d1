"""What the SDAR configuration brings to the benchmark: its file against the
source, the cut written down, the arithmetic its metrics divide by against
hand-worked numbers (per data token: both copies through every layer, the
head over the noised half, attention by kept pairs), its mix, and its readers.
The cases the manifest and arithmetic tests of this directory would take for a
new configuration stand here: a PR that adds a configuration edits no file the
benchmark already has. The reference against the program at nano size and the
cell's CPU rehearsal are in `tests/test_sdar.py`: this directory's tests run
once more inside `test_benchmark_widening.py`, under a time limit."""

import copy
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness.manifest import Manifest, problems, reduced_problems  # noqa: E402
from benchmark.models import sdar  # noqa: E402
import listed_readings  # noqa: E402
from widened_manifest import named_run  # noqa: E402,F401  (fixture)

CONFIG, CELL = "sdar-30b-a3b-chat-ep8", "sdar-30b-a3b-chat-ep8.fed8k"
# The catalog row of SDAR-30B-A3B-Chat (`model-configs` guide): the source's config.json.
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 32768,
    "max_window_layers": 48, "mlp_only_layers": [], "model_type": "sdar_moe", "moe_intermediate_size": 768,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
ATTENTION = 2 * 2048 * 4096 + 2 * 2048 * 512  # 18,874,368: W_q, W_o, W_k, W_v
EXPERT = 3 * 2048 * 768  # 4,718,592
SEQ, BLOCK = 8192, 4
KEPT = SEQ * SEQ + SEQ * BLOCK  # 67,141,632 of the doubled row's 268,435,456


@pytest.fixture(scope="module")
def config():
    return Manifest().config(CONFIG)


# ------------------------------------------------------------------ manifest
def test_the_manifest_has_no_problem_with_the_new_entries():
    m = Manifest()
    assert problems(m) == []
    # The eighth cell (a later one may follow it): `max(1, cells // 4)` opens a second four-chip slot (PR 51 took it).
    assert [w["name"] for w in m.data["workloads"]][7] == CELL
    assert [w["name"] for w in m.data["workloads"] if w["chips"] == 4][0] == "gpt2-xl-fsdp4.fed"
    entry = next(c for c in m.data["configs"] if c["name"] == CONFIG)
    assert reduced_problems(entry, m.config(CONFIG)) == []
    assert os.path.isfile(os.path.join(m.dir, "models", m.config(CONFIG)["model"] + ".py"))
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024
    assert all(1 <= len(e["why"]) <= 200 for e in m.data["configs"] + m.data["workloads"])


def test_the_file_holds_every_published_key_and_cuts_three_counts_and_no_width(config):
    differ = {k for k, v in PUBLISHED.items() if config.get(k, "missing") != v}
    assert differ == set(REDUCED) and config["reduced"] == REDUCED
    assert config["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == (5, 16, 18992)
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"] and config["first_expert_held"] == 0
    assert config["num_experts"] * 8 == sdar.router_width(config) == 128  # the router keeps the published width
    entry = next(c for c in Manifest().data["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == REDUCED and entry["source"] == config["source"]
    assert "eight" in config["layout"]["deployment"] and "not run" in config["layout"]["deployment"]
    assert config["batch"] == {**config["batch"], "global_rows": 1, "seq": SEQ}
    assert config["remat_policy"] == "save_attn" and config["rehearse_with"] == "sdar-nano"
    assert (config["block_length"], config["noise_eps"]) == (BLOCK, 1e-3)
    # The mask token is an id of the slice, and not the separator the loop takes (`vocab_size - 1`).
    assert 0 <= config["mask_token_id"] < config["vocab_size"] - 1
    for said in ("objective", "block_length", "noise_eps", "shift", "mask_token_id", "qk_norm", "rope", "aux_loss",
                 "optimizer", "learning_rate", "init", "loss_band"):
        assert len(config["assumed"][said]) > 40, said
    lo, hi = config["loss_band"]
    assert lo < 9.85 + 0.9 ** 2 / 2 < hi  # ln(18,992) and half the logits' variance: not that over two
    cell = Manifest().cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "fed8k", 1)
    assert "check_tolerances" not in config and "check_tolerances" in Manifest().config("sdar-nano")
    # The two measures against the reference at the stated precision stand between their readings on the chip
    # (PERF.md section 6): the system's largest and the precision below's least; the system's and the least planted
    # fault's. The four scalars stand above every reading of either, and hold another objective out.
    assert 1.3 * 1.412e-3 < sdar.CE_STATED_ABS_MEAN_TOL < 2.370e-3 / 1.2
    assert 2 * 1.076e-2 < sdar.QK_GRAD_STATED_REL_TOL < 4.879e-2 / 2
    for limit, system, bf16 in ((sdar.LOSS_ABS_TOL, 1.11e-3, 1.73e-3), (sdar.GRAD_NORM_REL_TOL, 1.41e-3, 4.81e-3),
                                (sdar.QK_GRAD_NORM_REL_TOL, 1.29e-3, 4.49e-3), (sdar.FLIPPED_SHARE_TOL, 8.9e-3, 5.4e-3)):
        assert limit >= 2 * system and limit > 0.8 * bf16
    assert set(sdar.LIMITS) == {"ce_stated_abs_mean", "qk_grad_stated_rel", "loss_abs", "grad_norm_rel",
                                "qk_grad_norm_rel", "flipped_share"}


def test_the_mix_is_long_documents_in_blocks_of_eight_rows():
    mix = Manifest().traffic("fed8k")
    assert mix["loop"] == "fed" and mix["block_rows"] == 8 and mix["supply_factor"] == 4
    assert mix["documents"] == {"median_tokens": 1000, "sigma": 1.6, "min_tokens": 8, "max_tokens": 16384}
    import numpy as np

    from benchmark.harness import traffic

    lengths = traffic.document_lengths(mix["documents"], 200_000, np.random.default_rng(2147493039))
    assert 900 < np.median(lengths) < 1100 and lengths.min() >= 8 and lengths.max() == 16384
    assert 0.05 < (lengths >= SEQ).mean() < 0.15  # some documents span a row
    blocks = traffic.make_document_blocks(mix["documents"], 2147493039, 40 * (SEQ + 1), 8, SEQ + 1, 18991)
    rows = traffic.pack_documents(blocks[0], row_tokens=SEQ + 1, eot_id=18991)["tokens"]
    assert rows.shape[0] in (8, 9) and rows.shape[1] == SEQ + 1 and rows.max() == 18991 and rows.min() >= 0


# ------------------------------------------------------------------ arithmetic
def test_the_arithmetic_by_hand(config):
    """What this chip computes a step: one row of 8,192 data tokens is 16,384 positions in every layer."""
    from benchmark.harness import peaks

    assert sdar.attention_matmul_params(config) == ATTENTION == 18_874_368
    per_layer = ATTENTION + 2048 * 128 + 16 * EXPERT + 2 * 2048 + 2 * 128  # 94,638,336: the issue's 94.64 M
    by_hand = 2 * 18992 * 2048 + 2048 + 5 * per_layer
    assert sdar.num_params(config) == by_hand == 550_984_960  # 8.82 GB at 16 B a parameter
    assert sdar.kept_pairs(config, SEQ) == KEPT == 67_141_632
    assert sdar.kept_pairs(config, 32) == 32 * 32 + 32 * 4
    assert sdar.held_pairs_per_layer(config, 2 * SEQ) == 16384  # 1,024 an expert
    active = 2 * 5 * (ATTENTION + 2048 * 128 + 1.0 * EXPERT) + 18992 * 2048
    assert sdar.active_matmul_params(config) == active == 277_446_656
    per_token = 6.0 * active + 5 * 12.0 * 32 * 128 * KEPT / SEQ
    assert sdar.train_flops_per_token(config, SEQ) == per_token == pytest.approx(3.679e9, rel=1e-3)
    assert sdar.flash_flops_per_step(config, 1, SEQ) == 5 * 32 * 12.0 * 128 * KEPT == pytest.approx(16.5e12, rel=1e-2)
    act, stat = 2 * SEQ * 128 * 2, 2 * SEQ * 4
    assert sdar.flash_bytes_per_step(config, 1, SEQ) == 5 * (
        32 * (2 * act + stat) + 4 * 2 * act + 32 * (4 * act + 2 * stat) + 4 * 4 * act)
    assert sdar.moe_expert_flops_per_step(config, 1, SEQ) == 5 * 16384 * 18.0 * 2048 * 768
    assert sdar.moe_expert_bytes_per_step(config, 1, SEQ) == 5 * 18.0 * (16384 * 2048 + 16 * 2048 * 768 + 16384 * 768)
    v5e = peaks.peaks_for("TPU v5 lite")
    compute = sdar.flash_flops_per_step(config, 1, SEQ) / v5e["bf16_flops_per_s"]
    memory = sdar.flash_bytes_per_step(config, 1, SEQ) / v5e["hbm_bytes_per_s"]
    assert compute == pytest.approx(83.7e-3, rel=1e-2) and compute > 10 * memory  # the kernels are the MXU's


def test_live_tile_pairs_from_the_table_and_the_programs_walk(config):
    """Of 4 n^2 tile pairs about n^2 + 2 n hold a kept score; the program's schedule walks those and no other."""
    from ray_tpu.ops.flash_attention import BlockDiffusion, kernel_plan

    assert sdar.live_tile_pairs(config, SEQ, 512, 1024) == 72 + 72 + 16 == 160
    assert sdar.live_tile_pairs(config, SEQ, 512, 512) == 2 * 136 + 16
    assert sdar.live_tile_pairs(config, 32, 64, 64) == 1  # the nano row: one tile spans both copies
    # Tiles of four blocks: the clean quadrant's lower 3, the strict quadrant's 3 (its diagonal tiles hold the
    # later blocks' view of the earlier), the noised diagonal's 2.
    assert sdar.live_tile_pairs({"block_length": 4}, 32, 16, 16) == 3 + 3 + 2
    plan = kernel_plan((1, 32, 2 * SEQ, 128), BlockDiffusion(SEQ, BLOCK), kv_heads=4)
    assert plan.tiles_visited == sdar.live_tile_pairs(config, SEQ, plan.tile_q, plan.tile_k)


def test_the_dense_mask_of_the_reference_is_the_issues_table():
    import numpy as np

    import jax.numpy as jnp

    got = np.asarray(sdar.dense_mask(jnp.arange(16), 8, 4))
    block = np.ones((4, 4), bool)
    none = np.zeros((4, 4), bool)
    want = np.block([[block, none, none, none], [block, block, none, none],
                     [none, none, block, none], [block, none, none, block]])
    assert np.array_equal(got, want)


def test_the_programs_own_count_agrees(config):
    from ray_tpu.models import sdar as program

    cfg = sdar.model_config(config)
    assert program.train_flops_per_token(cfg, SEQ) == pytest.approx(sdar.train_flops_per_token(config, SEQ), rel=1e-12)
    assert program.num_params(cfg) == sdar.num_params(config)
    assert program.kept_pairs(SEQ, cfg.block_length) == sdar.kept_pairs(config, SEQ)
    assert (cfg.n_head, cfg.n_kv_head, cfg.head_dim, cfg.n_experts, cfg.held) == (32, 4, 128, 128, 16)
    assert (cfg.block_length, cfg.mask_token_id, cfg.noise_eps, cfg.rope_theta) == (4, 18990, 1e-3, 1e6)
    assert config["router_init_tiles"] == 8 and not hasattr(cfg, "router_tiles")  # the benchmark's start, no option of the model's


def test_the_attention_path_of_the_cell_is_the_kernels_on_the_doubled_row(config):
    from ray_tpu.ops.flash_attention import select_backend

    class Cfg:
        n_head, head_dim = 32, 128

    system = sdar.System.__new__(sdar.System)
    system.cfg = Cfg
    assert system.attention_path(1, SEQ, "tpu") == select_backend((1, 32, 2 * SEQ, 128), "tpu") == "pallas"
    assert system.attention_path(1, SEQ, "cpu") == "xla"
    assert system.attention_path(1, 2 * SEQ, "tpu") == "blockwise"  # a row of 16,384 would be 32,768 positions


# ------------------------------------------------------------------ readers
NEW = ("bd.walked_over_live_tiles",)
# Twelve of the thirteen listed readings the Keye cell reports (PR 47 had room for eight; PR 50 folded the copies and the
# cell is on the other four's lists). Readings that cannot move (the share the check's one draw masked, the held share
# under tiled routers: `moe.held_pairs_share` would read 0.125 in every run; the draw's two adds) take no entry: they
# are in the check's summary, and `tests/test_sdar.py` holds them.
LISTED = ("data.wait_ms", "host.h2d_ms", "host.report_ms", "host.report_put_ms",
          "moe.router_ms", "moe.dispatch_ms", "moe.experts_ms", "moe.experts_roofline",
          "moe.load_max_over_mean", "kernels.gmm_ms", "kernels.gmm_roofline", "moe.issued_over_held")


def test_the_cell_is_on_the_list_of_each_listed_reading_it_reports_and_the_new_ones_list_the_cell():
    by_name, unlisted = listed_readings.holds_for(CELL, LISTED, NEW)
    assert {by_name[name]["layer"] for name in NEW} == {"block diffusion"}
    assert {"kernels.flash_ms", "kernels.flash_fwd_ms", "kernels.flash_bwd_ms", "kernels.flash_roofline",
            "step.mfu_pct"} <= unlisted
    # No stall reading (some 45 steps a window), no block-pull reading (PERF.md section 3), no held share (0.125 always).
    assert not {n for n in ("host.stall_pct", "data.fetch_block_ms", "moe.held_pairs_share")
                if CELL in listed_readings.TABLE[n]}


def test_the_new_readers_return_nothing_on_a_program_without_the_scope_or_the_counters(named_run):
    readers = Manifest().layer_readers()
    run = dict(named_run, summary={**named_run["summary"], "check": {}}, peaks={
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert [readers[name].read(run) for name in NEW] == [None] * len(NEW)  # gpt2: no such scope or counter
    assert [readers[name].read({"summary": {"check": {}}, "device_trace": None, "peaks": None})
            for name in NEW] == [None] * len(NEW)  # not traced


def test_the_walk_is_read_off_the_traced_kernels_and_the_shares_divide_by_this_models_counts(named_run, config):
    """The recorded tiny-GPT trace with its flash kernels under `tiles_<walked>of<all>`: the reader holds each against
    the live pairs at its own tiles and gives the largest. `kernels.flash_roofline`, `step.mfu_pct` and the expert
    layer's shares, unedited, divide by this file's counts (kept pairs; the even router's held pairs)."""
    from benchmark.harness import program_trace

    readers = Manifest().layer_readers()
    base = dict(named_run)
    program = program_trace.of(base)
    renamed = copy.copy(program)
    walks = {"flash_fwd": "tiles_330of1024/group_8/flash_fwd", "flash_bwd": "tiles_160of512/flash_bwd"}
    renamed.scopes = {k: v.replace("/attention/" + k.split(".")[0], "/attention/" + walks.get(k.split(".")[0], ""))
                      for k, v in program.scopes.items()}
    mask = {"live_tiles_of_all": {"512": 160, "1024": 300, "2048": 580}}
    run = {**base, "program_trace": renamed, "config": {**config, "model": "sdar"},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "summary": {**base["summary"], "device": {"count": 1}, "check": {"block_diffusion": mask}}}
    assert readers["bd.walked_over_live_tiles"].read(run) == 330 / 300  # the forward's, at its halved Q tile
    renamed.scopes = {k: v.replace("tiles_330of1024", "tiles_300of1024") for k, v in renamed.scopes.items()}
    assert readers["bd.walked_over_live_tiles"].read(run) == 1.0
    renamed.scopes = {k: v.replace("tiles_300of1024", "tiles_300of999") for k, v in renamed.scopes.items()}
    assert readers["bd.walked_over_live_tiles"].read(run) == 1.0  # tiles the check did not count: the backward's alone
    took = program_trace.flash_ms_of(run)
    assert readers["kernels.flash_roofline"].read(run) == pytest.approx(
        100 * sdar.flash_flops_per_step(config, 1, SEQ) / 197e12 * 1e3 / took)
    trace = run["device_trace"]
    assert readers["step.mfu_pct"].read(run) == pytest.approx(
        100 * sdar.train_flops_per_token(config, SEQ) * trace.host_steps * SEQ / trace.window_s / 197e12)
    assert readers["moe.experts_roofline"].read(run) is None  # the recorded step has no expert layer
    assert readers["kernels.gmm_roofline"].read(run) is None
