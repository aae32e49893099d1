"""What the LFM2 configuration brings to the benchmark: its file against the
source, the cut written down, the arithmetic its metrics divide by against
hand-worked numbers (what this chip computes, not what the model has), its
readers, and the cell's CPU rehearsal."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness.manifest import Manifest, problems  # noqa: E402
from benchmark.models import lfm2  # noqa: E402
import listed_readings  # noqa: E402
from widened_manifest import named_run  # noqa: E402,F401  (fixture)

CONFIG, CELL = "lfm2-24b-a2b-ep8-l5", "lfm2-24b-a2b-ep8-l5.fed4k"
FORTY = (["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 9 + ["full_attention", "conv"])
# The catalog row of LFM2-24B-A2B (`model-configs` guide): the source's config.json.
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 11776,
    "layer_types": FORTY, "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
}
REDUCED = ["num_hidden_layers", "num_dense_layers", "layer_types", "num_experts", "vocab_size"]


@pytest.fixture(scope="module")
def config():
    return Manifest().config(CONFIG)


def test_the_manifest_has_no_problem_with_the_new_entries():
    m = Manifest()
    assert problems(m) == []
    # The fifth cell (a later one may follow it); the first four-chip cell is GPT-2's (a later one may follow that too).
    assert [w["name"] for w in m.data["workloads"]][4] == CELL
    assert [w["name"] for w in m.data["workloads"] if w["chips"] == 4][0] == "gpt2-xl-fsdp4.fed"


def test_the_file_holds_every_published_key_and_cuts_five_counts_and_no_width(config):
    differ = {k for k, v in PUBLISHED.items() if config.get(k, "missing") != v}
    assert differ == set(REDUCED) and config["reduced"] == REDUCED
    assert config["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert (config["num_hidden_layers"], config["num_dense_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 1, 8, 8192)
    # One leading dense layer, then one whole period of the published pattern.
    assert config["layer_types"] == ["conv"] + FORTY[2:6] and len(config["layer_types"]) == 5
    assert lfm2.router_width(config) == 64  # the router keeps the published width
    entry = next(c for c in Manifest().data["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == REDUCED and entry["source"] == config["source"]
    assert "eight" in config["layout"]["deployment"]
    assert config["batch"] == {**config["batch"], "seq": 4096} and config["batch"]["global_rows"] in (4, 8)
    cell = Manifest().cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "fed4k", 1)


def test_flops_and_bytes_by_hand(config):
    """What this chip computes. A token meets: the dense layer 4 x 2048^2 + 3 x
    2048 x 11776 = 89,128,960; the period's operators 2 x 2048^2 + 2 x 2048 x 512
    + 3 x 4 x 2048^2 = 60,817,408; four routers of 2048 x 64; of its 4 experts a
    layer the 8/64 held here, 0.5 x 3 x 2048 x 1536 a layer; the head's 8,192
    rows. Attention: one layer of 32 heads, 6 products x 2 x 4096^2 x 64 / 2 a
    head. Experts: 16,384 expected pairs a layer. `conv_mix`: 11 passes of
    32,768 x 2048 bf16 a conv layer (forward 4, backward 7; the recomputation's 4 have not run since PR 36), four of them."""
    rows, seq = 8, 4096
    active = 89_128_960 + 60_817_408 + 4 * 131_072 + 4 * 0.5 * 9_437_184 + 8_192 * 2_048
    assert lfm2.active_matmul_params(config) == active == 186_122_240
    assert lfm2.train_flops_per_token(config, seq) == 6.0 * active + 12 * 2048 * 4096
    assert lfm2.held_pairs_per_layer(config, rows * seq) == 16_384
    assert lfm2.flash_flops_per_step(config, rows, seq) == 8 * 32 * 6 * 2 * 4096 * 4096 * 64 / 2
    assert lfm2.flash_bytes_per_step(config, rows, seq) == 8 * 32 * (11 * 4096 * 64 * 2 + 3 * 4096 * 4)
    assert lfm2.moe_expert_flops_per_step(config, rows, seq) == 4 * 16_384 * 18.0 * 2048 * 1536
    assert lfm2.moe_expert_bytes_per_step(config, rows, seq) == 4 * 18.0 * (
        16_384 * 2048 + 8 * 2048 * 1536 + 16_384 * 1536)
    assert lfm2.conv_mix_bytes_per_step(config, rows, seq) == 4 * 11 * 32_768 * 2048 * 2
    shares = {"dense": 89_128_960, "convs": 4 * 4 * 2048 ** 2, "experts": 4 * 0.5 * 9_437_184}
    assert {k: round(100 * v / active) for k, v in shares.items()} == {"dense": 48, "convs": 36, "experts": 10}
    assert round(100 * 3 * 2048 * 11776 / active) == 39  # the dense SwiGLU alone


def test_the_programs_own_count_agrees(config):
    from ray_tpu.models import lfm2 as program

    cfg = lfm2.lfm2_config(config)
    assert program.train_flops_per_token(cfg, 4096) == lfm2.train_flops_per_token(config, 4096)
    assert 468e6 < program.num_params(cfg) < 470e6  # 469 M here: 7.5 GB at 16 B a parameter
    assert (cfg.head_dim, cfg.group_size, cfg.n_experts, cfg.held) == (64, 4, 64, 8)
    assert program.layout(cfg) == (("conv_dense",), (
        "full_attention_moe", "conv_moe", "conv_moe", "conv_moe"), 1, ())


def test_the_reference_walks_the_tree_in_the_published_order():
    import jax

    from ray_tpu.models import LFM2Config
    from ray_tpu.models import lfm2 as program

    cfg = LFM2Config.nano(layer_types=tuple(FORTY[:12]))  # 2 + two periods + (attention, conv)
    c = {"layer_types": FORTY[:12], "num_dense_layers": 2}
    params = program.init_params(cfg, jax.random.PRNGKey(0))
    walked = [(op, ffn) for op, ffn, _ in lfm2.layers_in_order(params["blocks"], c)]
    assert [f"{op}_{ffn}" for op, ffn in walked] == list(program.layer_kinds(cfg))
    theirs = [jax.tree.map(lambda s: s.shape, layer)
              for _, _, layer in lfm2.layers_in_order(params["blocks"], c)]
    mine = [jax.tree.map(lambda s: s.shape, layer)
            for _, layer in program.pattern(cfg).layers(params["blocks"])]
    assert theirs == mine


# ------------------------------------------------------------------ readers
NEW = ("conv.short_conv_ms", "conv.mix_ms", "conv.mix_roofline")
BROUGHT = ("step.dense_mlp_ms", "moe.held_pairs_share", "moe.issued_over_held")  # PR 35's; later cells are on their lists
LISTED = ("data.wait_ms", "host.h2d_ms", "host.report_ms", "host.report_put_ms", "data.fetch_block_ms",
          "moe.router_ms", "moe.dispatch_ms", "moe.experts_ms", "moe.experts_roofline",
          "moe.load_max_over_mean", "kernels.gmm_ms", "kernels.gmm_roofline") + BROUGHT


def test_the_cell_is_on_the_list_of_each_listed_reading_it_reports_and_the_new_ones_list_the_cell():
    """The one cell on `data.fetch_block_ms`'s list: 8 rows a step out of packed blocks of 16 or 17 rows
    is a pull every second step, at most every third: four in any window of 8 traced steps."""
    listed_readings.holds_for(CELL, LISTED, NEW)
    assert listed_readings.TABLE["data.fetch_block_ms"] == [CELL]


def test_the_cell_brings_no_stall_reading_because_a_traced_window_of_its_steps_has_none():
    """`host.stall_pct` wants three readings at each of the clock's eight positions clear of the
    traced steps; a traced window of this cell has 32 steps of 0.6 s (my chip run, PR 35), which
    leaves two. A line that lacks a metric its cell lists is refused, so the cell does not list it
    (as the four-chip cell does not)."""
    from benchmark.harness.clock import TRACE_STEPS, WindowClock

    assert CELL not in next(e for e in Manifest().data["per_layer"] if e["name"] == "host.stall_pct")["workloads"]
    clock = WindowClock(20.0, 8 * 4096, (0, 20))
    clock.completed_at = [0.6 * (i + 1) for i in range(32)]
    assert clock.stall_share() is not None  # untraced: four readings a position
    clock.traced_steps = (14, 14 + TRACE_STEPS - 1)
    assert clock.stall_share() is None


def test_the_new_readers_return_nothing_on_a_program_without_the_scopes(named_run):
    readers = Manifest().layer_readers()
    run = dict(named_run, summary={**named_run["summary"], "check": {}}, peaks={
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    names = NEW + BROUGHT
    assert [readers[name].read(run) for name in names] == [None] * len(names)  # gpt2: no such scope


def test_the_counters_read_the_checks_routing():
    readers = Manifest().layer_readers()
    run = {"summary": {"check": {"routing": {"held_pairs_share": 0.126, "issued_over_held": 1.09}}}}
    assert readers["moe.held_pairs_share"].read(run) == 0.126
    assert readers["moe.issued_over_held"].read(run) == 1.09
    assert readers["moe.issued_over_held"].read({"summary": {"check": {"ok": True}}}) is None
    # 8 groups of 2,048 rows on block edges issue nothing extra; ragged ones a block each at most.
    assert lfm2._issued_rows([[2048] * 8]) == 9 * 16_384
    ragged = [[2000, 2100, 1990, 2050, 2075, 2011, 2089, 2069]]
    assert 9 * 16_384 < lfm2._issued_rows(ragged) <= 9 * 16_384 + 3 * 8 * (2 * 64 + 128)


# ---------------------------------------------------------------- rehearsal
def test_the_cells_cpu_rehearsal_prints_the_contracts_line():
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "2147493005",
         "--seconds", "2", "--trace", "1", "--rehearse-cpu"],
        cwd=REPO, capture_output=True, text=True, timeout=400)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 3
    assert line["device"]["platform"] == "cpu" and "platform=cpu" in proc.stdout
    assert all(name.startswith("rehearsal.") for name in line["metrics"])
    assert "rehearsal.data.wait_ms" in line["metrics"]
    assert 0.0 < line["metrics"]["rehearsal.moe.held_pairs_share"]["value"] < 0.6
    assert line["metrics"]["rehearsal.moe.issued_over_held"]["value"] >= 1.0
    assert '"dropped": 0' in proc.stdout and "expert_choices_flipped_share" in proc.stdout
