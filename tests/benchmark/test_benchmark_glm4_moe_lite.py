"""What the GLM-4.7-Flash configuration brings to the benchmark: its file against
the source, the cut written down, the arithmetic its metrics divide by against
hand-worked numbers (what this chip computes, not what the model has), the
its readers. The cases the manifest and arithmetic tests of this directory would
take for a new configuration stand here: a PR that adds a configuration edits
no file the benchmark already has. The reference against the program at nano
size and the cell's CPU rehearsal, which cost a minute, are in
`tests/test_glm4_moe_lite.py`: this directory's tests run once more inside
`test_benchmark_widening.py`, under a time limit."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness.manifest import Manifest, problems, reduced_problems  # noqa: E402
from benchmark.models import glm4_moe_lite as glm  # noqa: E402
import listed_readings  # noqa: E402
from widened_manifest import named_run  # noqa: E402,F401  (fixture)

CONFIG, CELL = "glm-4.7-flash-ep8-l5", "glm-4.7-flash-ep8-l5.fed4k"
# The catalog row of GLM-4.7-Flash (`model-configs` guide): the source's config.json.
PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 10240,
    "max_position_embeddings": 202752, "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True, "num_attention_heads": 20, "n_group": 1,
    "topk_group": 1, "n_routed_experts": 64, "n_shared_experts": 1, "routed_scaling_factor": 1.8,
    "num_experts_per_tok": 4, "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 1000000, "tie_word_embeddings": False,
    "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880,
}
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
ATTENTION = 2048 * 768 + 768 * 20 * 256 + 2048 * 576 + 512 * 20 * 448 + 20 * 256 * 2048  # 21,757,952
EXPERT = 3 * 2048 * 1536  # 9,437,184


@pytest.fixture(scope="module")
def config():
    return Manifest().config(CONFIG)


@pytest.fixture(scope="module")
def nano():
    return Manifest().config("glm4-moe-lite-nano")


# ------------------------------------------------------------------ manifest
def test_the_manifest_has_no_problem_with_the_new_entries():
    m = Manifest()
    assert problems(m) == []
    # The sixth cell (a later one may follow it); the first four-chip cell is GPT-2's (a later one may follow that too).
    assert [w["name"] for w in m.data["workloads"]][5] == CELL
    assert [w["name"] for w in m.data["workloads"] if w["chips"] == 4][0] == "gpt2-xl-fsdp4.fed"
    entry = next(c for c in m.data["configs"] if c["name"] == CONFIG)
    assert reduced_problems(entry, m.config(CONFIG)) == []
    assert os.path.isfile(os.path.join(m.dir, "models", m.config(CONFIG)["model"] + ".py"))


def test_the_file_holds_every_published_key_and_cuts_three_counts_and_no_width(config):
    differ = {k for k, v in PUBLISHED.items() if config.get(k, "missing") != v}
    assert differ == set(REDUCED) and config["reduced"] == REDUCED
    assert config["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert (config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"]) == (5, 8, 19360)
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"] and config["first_expert_held"] == 0
    assert glm.router_width(config) == 64  # the router keeps the published width
    entry = next(c for c in Manifest().data["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == REDUCED and entry["source"] == config["source"]
    assert "eight" in config["layout"]["deployment"] and "prediction module" in config["layout"]["deployment"]
    assert config["batch"] == {**config["batch"], "global_rows": 2, "seq": 4096}
    assert config["remat_policy"] == "save_attn" and config["rehearse_with"] == "glm4-moe-lite-nano"
    memory = config["memory_analysis_v5e_bytes"]
    assert 0 <= memory["total"] - memory["arguments"] - memory["temporaries"] < 1 << 20
    assert 0.25 * 16_909_336_064 < memory["peak_memory"] < 16_909_336_064
    cell = Manifest().cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "fed4k", 1)


# ---------------------------------------------------------------- arithmetic
def test_flops_bytes_and_parameters_by_hand(config):
    """What this chip computes. A token meets six attention calls' five matrices
    (21,757,952 each: five layers and the module's), the dense SwiGLU 3 x 2048 x
    10240, five routers of 2048 x 64, five shared experts of 3 x 2048 x 1536, of
    its 4 routed experts a layer the 8/64 held here (0.5 x 9,437,184), the head's
    19,360 rows twice and the module's 4096 x 2048 projection. Attention: 20
    heads of 256, six calls, 6 products x 2 x 4096^2 x 256 / 2 a head. Experts:
    4,096 expected pairs a layer."""
    rows, seq = 2, 4096
    moe = 2048 * 64 + EXPERT + 0.5 * EXPERT
    active = 6 * ATTENTION + 3 * 2048 * 10240 + 5 * moe + 2 * 19360 * 2048 + 2 * 2048 * 2048
    assert glm.attention_matmul_params(config) == ATTENTION == 21_757_952
    assert glm.active_matmul_params(config) == active == 352_583_680
    assert glm.train_flops_per_token(config, seq) == 6.0 * active + 12 * 6 * 20 * 256 * 4096 == 3_625_451_520
    assert glm.held_pairs_per_layer(config, rows * seq) == 4096
    assert glm.flash_flops_per_step(config, rows, seq) == 2 * 20 * 6 * (6 * 2 * 4096 * 4096 * 256 / 2)
    assert glm.flash_bytes_per_step(config, rows, seq) == 2 * 20 * 6 * (11 * 4096 * 256 * 2 + 3 * 4096 * 4)
    assert glm.moe_expert_flops_per_step(config, rows, seq) == 5 * 4096 * 18.0 * 2048 * 1536
    assert glm.moe_expert_bytes_per_step(config, rows, seq) == 5 * 18.0 * (
        4096 * 2048 + 8 * 2048 * 1536 + 4096 * 1536)
    # 706 M parameters: per attention four norms too, per expert layer the bias and 8 + 1 experts.
    layer = ATTENTION + 2 * 2048 + 768 + 512
    held = 2048 * 64 + 64 + 9 * EXPERT
    by_hand = (2 * 19360 * 2048 + 2048 + 6 * layer + 3 * 2048 * 10240 + 5 * held
               + 2 * 2048 * 2048 + 3 * 2048)
    assert glm.num_params(config) == by_hand == 706_518_848
    shares = {"flash": 12 * 6 * 5120 * 4096, "latent": 6 * 6 * ATTENTION, "experts": 6 * 5 * moe,
              "dense": 6 * 3 * 2048 * 10240, "heads": 6 * 2 * 19360 * 2048}
    total = glm.train_flops_per_token(config, seq)
    assert {k: round(100 * v / total) for k, v in shares.items()} == {
        "flash": 42, "latent": 22, "experts": 12, "dense": 10, "heads": 13}


def test_the_kernels_floor_is_compute_at_this_head(config):
    from benchmark.harness.peaks import peaks_for

    v5e = peaks_for("TPU v5 lite")
    compute = glm.flash_flops_per_step(config, 2, 4096) / v5e["bf16_flops_per_s"]
    memory = glm.flash_bytes_per_step(config, 2, 4096) / v5e["hbm_bytes_per_s"]
    assert compute == pytest.approx(31.39e-3, rel=1e-3)  # 6.185 TFLOP / 197 TFLOP/s
    assert memory == pytest.approx(6.77e-3, rel=1e-2)
    assert compute > memory


def test_the_programs_own_count_agrees(config):
    from ray_tpu.models import glm4_moe_lite as program

    cfg = glm.model_config(config)
    assert program.train_flops_per_token(cfg, 4096) == glm.train_flops_per_token(config, 4096)
    assert program.num_params(cfg) == glm.num_params(config)  # 706.5 M here: 11.3 GB at 16 B a parameter
    assert (cfg.head_dim, cfg.v_head_dim, cfg.n_experts, cfg.held, cfg.n_predict_layers) == (256, 256, 64, 8, 1)
    assert program.layer_kinds(cfg) == ("latent_dense",) + ("latent_moe",) * 4
    without = glm.model_config({**config, "num_nextn_predict_layers": 0})
    assert program.train_flops_per_token(without, 4096) == glm.train_flops_per_token(
        {**config, "num_nextn_predict_layers": 0}, 4096)
    assert program.num_params(without) == glm.num_params({**config, "num_nextn_predict_layers": 0})


def test_the_attention_path_of_the_cell_is_the_kernels(config):
    from ray_tpu.ops.flash_attention import select_backend

    cfg = glm.model_config(config)
    shape = (config["batch"]["global_rows"], cfg.n_head, config["batch"]["seq"], cfg.head_dim)
    assert shape == (2, 20, 4096, 256)
    assert select_backend(shape, "tpu") == "pallas" and select_backend(shape, "cpu") == "xla"


# ----------------------------------------------------------------- reference
def test_the_reference_walks_the_tree_in_the_published_order(nano):
    import jax

    from ray_tpu.models import glm4_moe_lite as program

    cfg = glm.model_config(nano)
    params = program.init_params(cfg, jax.random.PRNGKey(0))
    theirs = [jax.tree.map(lambda s: s.shape, layer) for layer in glm.layers_in_order(params, nano)]
    mine = [jax.tree.map(lambda s: s.shape, layer) for _, layer in program.pattern(cfg).layers(params["blocks"])]
    assert theirs == mine and len(theirs) == 3 and "moe" not in theirs[0] and "moe" in theirs[1]


# ------------------------------------------------------------------ readers
NEW = ("mtp.ms",)
# `moe.shared_ms` came with this cell and listed it alone until PR 65 put Solar-Open2's and Trinity-Mini's cells beside it,
# `mla.latent_ms` until PR 69 put Xing4.0's.
LISTED = ("data.wait_ms", "host.h2d_ms", "host.report_ms", "host.report_put_ms",
          "moe.router_ms", "moe.dispatch_ms", "moe.experts_ms", "moe.experts_roofline",
          "moe.load_max_over_mean", "kernels.gmm_ms", "kernels.gmm_roofline", "step.dense_mlp_ms",
          "moe.held_pairs_share", "moe.issued_over_held", "moe.shared_ms", "mla.latent_ms")


def test_the_cell_is_on_the_list_of_each_listed_reading_it_reports_and_the_new_ones_list_the_cell():
    by_name, unlisted = listed_readings.holds_for(CELL, LISTED, NEW)
    assert {by_name[name]["layer"] for name in NEW} == {"prediction module"}
    assert (by_name["moe.shared_ms"]["layer"], by_name["mla.latent_ms"]["layer"]) == ("expert layer", "latent attention")
    # Every unlisted reading of the accepted benchmark is the cell's too: the four `kernels.flash_*` among them.
    assert {"kernels.flash_ms", "kernels.flash_fwd_ms", "kernels.flash_bwd_ms", "kernels.flash_roofline"} <= unlisted
    # No stall reading and no block-pull reading: an entry lists a cell only where every traced line
    # carries it, and of two traced runs of this cell one held no pull inside its 8 steps (a packed
    # block is 16 or 17 rows, so a pull comes every eighth or ninth step: PERF.md section 3, PR 39).
    assert not {n for n in ("host.stall_pct", "data.fetch_block_ms") if CELL in listed_readings.TABLE[n]}


def test_the_new_readers_return_nothing_on_a_program_without_the_scopes(named_run):
    readers = Manifest().layer_readers()
    run = dict(named_run, summary={**named_run["summary"], "check": {}}, peaks={
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    names = NEW + ("mla.latent_ms", "moe.shared_ms", "step.dense_mlp_ms", "moe.held_pairs_share", "moe.issued_over_held")
    assert [readers[name].read(run) for name in names] == [None] * len(names)  # gpt2: no such scope


def test_the_counters_read_the_checks_routing():
    readers = Manifest().layer_readers()
    run = {"summary": {"check": {"routing": {"held_pairs_share": 0.126, "issued_over_held": 1.3}}}}
    assert readers["moe.held_pairs_share"].read(run) == 0.126
    assert readers["moe.issued_over_held"].read(run) == 1.3
    # 8 groups of 512 rows on block edges issue nothing extra; ragged ones a block each at most.
    assert glm._issued_rows([[512] * 8]) == 9 * 4096
    ragged = [[500, 530, 490, 515, 520, 505, 525, 511]]
    assert 9 * 4096 < glm._issued_rows(ragged) <= 9 * 4096 + 3 * 8 * (2 * 64 + 128)
