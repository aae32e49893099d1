"""What the Keye-VL-2.0 configuration brings to the benchmark: its file against
the source, the cut written down, the arithmetic its metrics divide by against
hand-worked numbers (what this chip computes, and of the attention what the
selection asks for, not what a walk over the causal half does), its mix, and
its readers. The cases the manifest and arithmetic tests of this directory
would take for a new configuration stand here: a PR that adds a configuration
edits no file the benchmark already has. The reference against the program at
nano size and the cell's CPU rehearsal are in `tests/test_keye_vl2.py`: this
directory's tests run once more inside `test_benchmark_widening.py`, under a
time limit."""

import copy
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness.manifest import Manifest, problems, reduced_problems  # noqa: E402
from benchmark.models import keye_vl2 as keye  # noqa: E402
import listed_readings  # noqa: E402
from widened_manifest import named_run  # noqa: E402,F401  (fixture)

CONFIG, CELL = "keye-vl-2.0-30b-a3b-ep8", "keye-vl-2.0-30b-a3b-ep8.fed16k"
# The catalog row of Keye-VL-2.0-30B-A3B (`model-configs` guide): the source's config.json.
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 262144,
    "max_window_layers": 48, "mlp_only_layers": [], "model_type": "KeyeVL2", "moe_intermediate_size": 768,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
                  "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936,
}
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
ATTENTION = 2 * 2048 * 4096 + 2 * 2048 * 512  # 18,874,368: W_q, W_o, W_k, W_v
INDEXER = 2048 * 16 * 64 + 2048 * 64 + 2048 * 16  # 2,260,992
EXPERT = 3 * 2048 * 768  # 4,718,592
SEQ = 16384
SELECTED = 2048 * 2049 // 2 + 14336 * 2048  # 31,458,304 of a head's 134,225,920 causal pairs


@pytest.fixture(scope="module")
def config():
    return Manifest().config(CONFIG)


# ------------------------------------------------------------------ manifest
def test_the_manifest_has_no_problem_with_the_new_entries():
    m = Manifest()
    assert problems(m) == []
    # The seventh cell (a later one may follow it); the first four-chip cell is GPT-2's (a later one may follow that too).
    assert [w["name"] for w in m.data["workloads"]][6] == CELL
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
    assert config["num_experts"] * 8 == keye.router_width(config) == 128  # the router keeps the published width
    entry = next(c for c in Manifest().data["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == REDUCED and entry["source"] == config["source"]
    assert "eight" in config["layout"]["deployment"] and "text" in config["layout"]["deployment"]
    assert config["batch"] == {**config["batch"], "global_rows": 1, "seq": SEQ}
    assert config["remat_policy"] == "save_attn" and config["rehearse_with"] == "keye-vl2-nano"
    for said in ("indexer_input", "indexer_rope", "indexer_k_norm", "indexer_weight_scale", "qk_norm", "mrope",
                 "selection_ties", "index_loss", "q_chunk_size/kv_chunk_size", "aux_loss", "scope"):
        assert len(config["assumed"][said]) > 40, said
    memory = config["memory_analysis_v5e_bytes"]
    assert 0 <= memory["total"] - memory["arguments"] - memory["temporaries"] < 1 << 20
    assert 0.8 * 16_909_336_064 < memory["peak_memory"] < 16_909_336_064
    cell = Manifest().cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "fed16k", 1)
    # The cell is held to the model file's limits, each between its two readings on the chip (the system's
    # largest of twelve, the bf16 reference's smallest of five: PERF.md section 6); only the toy carries its own.
    assert "check_tolerances" not in config and "check_tolerances" in Manifest().config("keye-vl2-nano")
    for limit, system, bf16 in ((keye.LOSS_ABS_TOL, 9.3e-5, 8.5e-4), (keye.GRAD_NORM_REL_TOL, 5.6e-5, 4.5e-4),
                                (keye.INDEX_LOSS_REL_TOL, 6.5e-5, 1.66e-3), (keye.FLIPPED_SHARE_TOL, 5.32e-3, 6.44e-3),
                                (keye.SELECTION_DIFFERS_TOL, 9.16e-3, 9.91e-3)):
        assert system < limit < bf16


def test_the_mix_is_long_documents_in_blocks_of_eight_rows():
    mix = Manifest().traffic("fed16k")
    assert mix["loop"] == "fed" and mix["block_rows"] == 8 and mix["supply_factor"] == 4
    assert mix["documents"] == {"median_tokens": 1000, "sigma": 1.6, "min_tokens": 8, "max_tokens": 32768}
    import numpy as np

    from benchmark.harness import traffic

    lengths = traffic.document_lengths(mix["documents"], 200_000, np.random.default_rng(2147493039))
    assert 900 < np.median(lengths) < 1100 and lengths.min() >= 8 and lengths.max() == 32768
    assert 0.02 < (lengths >= SEQ).mean() < 0.06  # some documents span a row
    blocks = traffic.make_document_blocks(mix["documents"], 2147493039, 40 * (SEQ + 1), 8, SEQ + 1, 18991)
    rows = traffic.pack_documents(blocks[0], row_tokens=SEQ + 1, eot_id=18991)["tokens"]
    assert rows.shape == (8, SEQ + 1) and rows.max() == 18991 and rows.min() >= 0


# ---------------------------------------------------------------- arithmetic
def test_flops_bytes_parameters_and_selected_pairs_by_hand(config):
    """What this chip computes. A layer outside its experts: 18.87 M of attention, 2.26 M of indexer,
    0.26 M of router: 21.4 M; an expert 4.72 M, the 16 held 75.5 M; embedding and head 2 x 18,992 x
    2,048. A token meets 8 x 16/128 = 1 held expert a layer in expectation. The selection keeps
    31.46 M of a head's 134.2 M causal pairs, 23.4 %."""
    rows = 1
    assert keye.attention_matmul_params(config) == ATTENTION + INDEXER == 21_135_360
    outside = ATTENTION + INDEXER + 2048 * 128
    assert outside == 21_397_504 and 16 * EXPERT == 75_497_472
    by_hand = 2 * 18992 * 2048 + 2048 + 5 * (outside + 16 * EXPERT + 2 * 2048 + 2 * 128 + 2 * 64)
    assert keye.num_params(config) == by_hand == 562_290_560  # 9.00 GB at 16 B a parameter
    assert keye.selected_pairs(config, SEQ) == SELECTED == 31_458_304
    assert SELECTED / (SEQ * (SEQ + 1) / 2) == pytest.approx(0.2344, abs=1e-4)
    assert keye.selected_pairs(config, 2048) == 2048 * 2049 // 2 and keye.selected_pairs(config, 64) == 64 * 65 // 2
    assert keye.held_pairs_per_layer(config, SEQ) == 16384  # 1,024 an expert
    active = 5 * (outside + 1.0 * EXPERT) + 18992 * 2048
    assert keye.active_matmul_params(config) == active == 169_476_096
    per_token = 6.0 * active + 5 * (14.0 * 32 * 128 * SELECTED / SEQ + 6.0 * 16 * 64 * (SEQ + 1) / 2)
    assert keye.train_flops_per_token(config, SEQ) == per_token
    assert per_token * SEQ == pytest.approx(2.98e13, rel=2e-3)  # 1.67e13 of parameters, 0.90e13 of selected pairs, 0.41e13 of indexer
    assert keye.flash_flops_per_step(config, rows, SEQ) == 5 * 32 * 12.0 * 128 * SELECTED == pytest.approx(7.73e12, rel=1e-3)
    act, stat = SEQ * 128 * 2, SEQ * 4
    assert keye.flash_bytes_per_step(config, rows, SEQ) == 5 * (
        32 * (6 * act + 3 * stat) + 4 * 6 * act + 2 * SEQ * SEQ / 8)
    assert keye.select_flops_per_step(config, rows, SEQ) == 5 * 2.0 * 16 * 64 * SEQ * (SEQ + 1) / 2
    assert keye.index_loss_flops_per_step(config, rows, SEQ) == 5 * (2.0 * 32 * 128 + 6.0 * 16 * 64) * SELECTED
    assert keye.moe_expert_flops_per_step(config, rows, SEQ) == 5 * 16384 * 18.0 * 2048 * 768
    assert keye.moe_expert_bytes_per_step(config, rows, SEQ) == 5 * 18.0 * (
        16384 * 2048 + 16 * 2048 * 768 + 16384 * 768)


def test_every_floor_is_under_what_a_causal_walk_costs(config):
    """A share of a floor that counts selected pairs cannot pass 100 % while the kernel walks the causal
    half: the walk's own FLOPs are 4.27 times the floor's."""
    from benchmark.harness.peaks import peaks_for
    from benchmark.models import gpt2

    v5e = peaks_for("TPU v5 lite")
    walked = gpt2.flash_flops_per_step({"n_embd": 4096, "n_head": 32, "n_layer": 5}, 1, SEQ)
    assert walked / keye.flash_flops_per_step(config, 1, SEQ) == pytest.approx(4.267, rel=1e-3)
    for flops, moved in ((keye.flash_flops_per_step, keye.flash_bytes_per_step),
                         (keye.index_loss_flops_per_step, keye.index_loss_bytes_per_step)):
        compute = flops(config, 1, SEQ) / v5e["bf16_flops_per_s"]
        memory = moved(config, 1, SEQ) / v5e["hbm_bytes_per_s"]
        assert compute > memory > 0
    assert keye.flash_flops_per_step(config, 1, SEQ) / v5e["bf16_flops_per_s"] == pytest.approx(39.2e-3, rel=1e-2)


def test_the_programs_own_count_agrees(config):
    from ray_tpu.models import keye_vl2 as program

    cfg = keye.model_config(config)
    assert program.train_flops_per_token(cfg, SEQ) == pytest.approx(keye.train_flops_per_token(config, SEQ), rel=1e-12)
    assert program.num_params(cfg) == keye.num_params(config)
    assert program.selected_pairs(SEQ, cfg.index_topk) == keye.selected_pairs(config, SEQ)
    assert (cfg.n_head, cfg.n_kv_head, cfg.head_dim, cfg.n_experts, cfg.held, cfg.index_topk) == (32, 4, 128, 128, 16, 2048)
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.mrope_section, cfg.rope_theta) == (16, 64, (16, 24, 24), 1e7)


def test_the_attention_path_of_the_cell_is_the_kernels(config):
    from ray_tpu.ops.flash_attention import kernel_plan, select_backend

    cfg = keye.model_config(config)
    shape = (config["batch"]["global_rows"], cfg.n_head, config["batch"]["seq"], cfg.head_dim)
    assert shape == (1, 32, SEQ, 128)
    assert select_backend(shape, "tpu") == "pallas" and select_backend(shape, "cpu") == "xla"
    assert kernel_plan(shape, kv_heads=4, keep=True) == (512, 1024, 272, 32, 512, False)


# ------------------------------------------------------------------ readers
NEW = ("dsa.indexer_ms", "dsa.select_ms", "dsa.index_loss_ms", "dsa.selected_share", "dsa.live_tiles_share",
       "kernels.select_ms", "kernels.select_roofline", "kernels.index_loss_ms", "kernels.index_loss_roofline")
LISTED = ("data.wait_ms", "host.h2d_ms", "host.report_ms", "host.report_put_ms",
          "moe.router_ms", "moe.dispatch_ms", "moe.experts_ms", "moe.experts_roofline",
          "moe.load_max_over_mean", "kernels.gmm_ms", "kernels.gmm_roofline",
          "moe.held_pairs_share", "moe.issued_over_held")


def test_the_cell_is_on_the_list_of_each_listed_reading_it_reports_and_the_new_ones_list_the_cell():
    by_name, unlisted = listed_readings.holds_for(CELL, LISTED, NEW)
    assert {by_name[name]["layer"] for name in NEW} == {"sparse attention", "kernels"}
    assert {"kernels.flash_ms", "kernels.flash_fwd_ms", "kernels.flash_bwd_ms", "kernels.flash_roofline",
            "step.mfu_pct"} <= unlisted
    # No stall reading (about 20 steps a window) and no block-pull reading (PERF.md section 3).
    assert not {n for n in ("host.stall_pct", "data.fetch_block_ms") if CELL in listed_readings.TABLE[n]}


def test_the_new_readers_return_nothing_on_a_program_without_the_scopes(named_run):
    readers = Manifest().layer_readers()
    run = dict(named_run, summary={**named_run["summary"], "check": {}}, peaks={
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert [readers[name].read(run) for name in NEW] == [None] * len(NEW)  # gpt2: no such scope, kernel or counter
    assert [readers[name].read({"summary": {"check": {}}, "device_trace": None, "peaks": None})
            for name in NEW] == [None] * len(NEW)  # not traced


def test_the_new_readers_read_their_scope_kernel_and_counter(named_run, config):
    """The recorded tiny-GPT trace with its names changed for this model's: `qkv` for a scope, `flash_fwd`
    for a kernel. Each reader then reads what the accepted reader of the old name reads."""
    from benchmark.harness import program_trace, scope_trace

    readers = Manifest().layer_readers()
    base = dict(named_run)
    program = program_trace.of(base)
    want_kernel = program.kernel("flash_fwd")
    assert want_kernel > 0
    for scope, kernel in (("indexer", None), ("select", "select"), ("index_loss", "index_loss")):
        renamed = copy.copy(program)
        renamed.scopes = {k: v.replace("/qkv/", f"/{scope}/").replace("/flash_fwd/", f"/{kernel}/")
                          for k, v in program.scopes.items()}
        run = {**base, "program_trace": renamed, "config": {**config, "model": "keye_vl2"},
               "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
               "summary": {**base["summary"], "device": {"count": 1}}}
        # The scope's reader takes whatever stands under it: the kernel of its name too.
        want_scope = scope_trace.scope_ms(base, ("qkv", "flash_fwd") if kernel else ("qkv",))
        assert readers[f"dsa.{scope}_ms"].read(run) == want_scope > 0
        if kernel:
            assert readers[f"kernels.{kernel}_ms"].read(run) == want_kernel
            floors = {"select": keye.select_flops_per_step, "index_loss": keye.index_loss_flops_per_step}
            share = readers[f"kernels.{kernel}_roofline"].read(run)
            assert share == pytest.approx(100 * floors[kernel](config, 1, SEQ) / 197e12 * 1e3 / want_kernel)
    run = {"summary": {"check": {"selection": {"selected_share": 0.2345, "live_tiles_share": 0.99},
                                 "routing": {"held_pairs_share": 0.126, "issued_over_held": 1.3}}}}
    assert readers["dsa.selected_share"].read(run) == 0.2345
    assert readers["dsa.live_tiles_share"].read(run) == 0.99
    assert readers["moe.held_pairs_share"].read(run) == 0.126
    assert readers["moe.issued_over_held"].read(run) == 1.3
