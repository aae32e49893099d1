"""What the Solar-Open2 configuration brings to the benchmark: its file against the catalog row, its cell and entries
appended and held to the contract, its readers on a recorded trace, the floors' arithmetic and the parameter count by
hand. A one-chip cell (both four-chip slots are taken). It brought the fourteen listed readings it reports as
`<metric>.<configuration>` copies (PR 59); PR 65 put it on those entries' own lists and deleted the copies
(`listed_readings.TABLE`).
(The cell's CPU rehearsal is `tests/test_solar_open2_rehearsal.py`: this directory's tests are run a second time
inside `test_benchmark_widening.py`.)"""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import listed_readings  # noqa: E402
from benchmark.harness.manifest import Manifest, problems, reduced_problems  # noqa: E402
from benchmark.models import solar_open2  # noqa: E402
from widened_manifest import named_run  # noqa: E402,F401  (fixture)

CONFIG = "solar-open2-250b-ep40-l4"
CELL = CONFIG + ".fed4k"
ROWS, SEQ, CHIPS = 1, 4096, 1
NEW = ("kda.mixer_ms", "kda.conv_ms", "kda.gates_ms", "kernels.kda_fwd_ms", "kernels.kda_bwd_ms", "kernels.kda_ms",
       "kernels.kda_roofline")
# The listed readings a one-chip fed cell of experts with a shared one reports in every traced run. Not
# `data.fetch_block_ms` (a block of 16 rows lasts 16 steps of a row: a window of 8 traced steps holds a pull one time
# in two), not `host.stall_pct` (about 160 steps a window: fewer than three readings a position clear of the traced
# ones), not `step.dense_mlp_ms` (no layer is dense).
LISTED = ("data.wait_ms", "host.h2d_ms", "host.report_ms", "host.report_put_ms", "moe.router_ms", "moe.dispatch_ms",
          "moe.experts_ms", "moe.experts_roofline", "moe.load_max_over_mean", "kernels.gmm_ms", "kernels.gmm_roofline",
          "moe.held_pairs_share", "moe.issued_over_held", "moe.shared_ms")
REDUCED = ["num_hidden_layers", "gqa_layers", "n_routed_experts", "num_attention_heads", "num_key_value_heads",
           "linear_attn_config", "vocab_size"]
V5E_HBM_BYTES = 16_909_336_064


@pytest.fixture(scope="module")
def config():
    return Manifest().config(CONFIG)


def test_the_manifest_holds_the_cell_appended_and_meets_the_contract():
    m = Manifest()
    assert problems(m) == []
    cells = [w["name"] for w in m.data["workloads"]]
    assert cells[9] == CELL and m.cell(CELL) == {**m.cell(CELL), "config": CONFIG, "traffic": "fed4k", "chips": 1}
    entry = m.data["configs"][8]
    assert entry["name"] == CONFIG and entry["reduced"] == REDUCED and reduced_problems(entry, m.config(CONFIG)) == []
    assert os.path.isfile(os.path.join(m.dir, "models", m.config(CONFIG)["model"] + ".py"))
    assert all(1 <= len(e["why"]) <= 200 for e in m.data["configs"] + m.data["workloads"])
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024
    # One run of seven after the 73 entries PR 56 left: the new readings (the fourteen copies that followed went in
    # PR 65, so PR 60's `step.xla_remat_ms` stands next).
    names = [e["name"] for e in m.data["per_layer"]]
    assert names[73:80] == list(NEW) and names[80] == "step.xla_remat_ms"
    assert not [name for name in names if name.endswith("." + CONFIG)]
    # The mix is the one that was there, unedited: rows of 4,097 out of 16-row blocks.
    assert m.traffic("fed4k") == {**m.traffic("fed4k"), "loop": "fed", "block_rows": 16, "supply_factor": 4}


def test_the_cell_reports_the_new_readings_each_listed_one_and_every_unlisted_one():
    m = Manifest()
    readers = m.layer_readers()
    by_name, unlisted = listed_readings.holds_for(CELL, LISTED, NEW)
    assert len(unlisted) >= 30
    for name in NEW:
        assert by_name[name]["moves"] == "tokens_per_s_per_chip"
        assert readers[name].META == {k: v for k, v in by_name[name].items() if k != "workloads"}
    assert by_name["kernels.kda_roofline"]["unit"] == "%" and by_name["kernels.kda_roofline"]["better"] == "higher"
    assert {by_name[n]["layer"] for n in NEW[:3]} == {"linear attention"} and by_name[NEW[3]]["layer"] == "kernels"
    assert [e["name"] for e in m.metrics_for(CELL, "end_to_end")] == ["tokens_per_s_per_chip", "setup_s"]


def test_the_file_holds_every_published_key_and_cuts_counts_and_no_width(config):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        published = next(json.loads(line) for line in fh if json.loads(line)["name"] == "Solar-Open2-250B")
    differ = {k for k, v in published["config"].items() if config.get(k, "missing") != v}
    assert differ == set(REDUCED) and config["reduced"] == REDUCED
    assert config["source"] == published["source_url"]
    assert config["published"] == {k: published["config"][k] for k in REDUCED}
    # Inside the one nested group the head count alone differs: no width.
    here, there = config["linear_attn_config"], published["config"]["linear_attn_config"]
    assert {k for k in there if here[k] != there[k]} == {"num_heads"} and (here["num_heads"], there["num_heads"]) == (8, 64)
    assert (config["num_hidden_layers"], config["gqa_layers"]) == (4, [0])
    assert solar_open2.layer_types(config) == ["gqa", "kda", "kda", "kda"]  # one whole period
    assert (config["n_routed_experts"], config["first_expert_held"], solar_open2.router_width(config)) == (8, 0, 320)
    assert (config["num_attention_heads"], config["num_key_value_heads"]) == (8, 1)
    assert config["vocab_size"] * 8 == 196608  # an eighth of the vocabulary, the guide's floor
    for width in ("hidden_size", "head_dim", "moe_intermediate_size", "intermediate_size", "num_experts_per_tok"):
        assert config[width] == published["config"][width]
    assert config["layout"] == {**config["layout"], "num_workers": 1, "tpus_per_worker": 1, "mesh": None}
    for said in ("forty-chip", "40-way", "8-way", "pipeline stages", "312 experts", "56 heads", "reference alike"):
        assert said in config["layout"]["deployment"], said
    assert config["batch"] == {**config["batch"], "global_rows": ROWS, "seq": SEQ}
    for said in ("102 tokens", "512", "a fifth", "841 M", "two rows do not fit"):
        assert said in config["batch"]["why"], said
    assert config["remat_policy"] == "save_attn" and config["rehearse_with"] == "solar-open2-nano"
    for said in ("kda_gate_rank", "linear_attn_config.num_kv_heads", "decay_gate", "beta", "output_gate", "q_k", "router",
                 "selection_bias_and_aux_loss", "use_gqa_gate", "attention", "block", "intermediate_size",
                 "initialisation", "optimizer", "n_routed_experts"):
        assert len(config["assumed"][said]) > 40, said
    memory = config["memory_analysis_v5e_bytes"]
    assert memory["arguments"] < memory["peak"] <= 16.0e9 and memory["peak"] > 0.25 * V5E_HBM_BYTES
    lo, hi = config["loss_band"]
    assert lo < 10.11 + 1.28 ** 2 / 2 < hi  # ln(24,576) and half the logits' variance at the seeded weights (0.02 x sqrt(4,096))
    assert "check_tolerances" not in config and "check_tolerances" in Manifest().config("solar-open2-nano")


def test_the_parameter_count_by_hand(config):
    d, wide, rank, f = 4096, 8 * 128, 128, 1280
    kda = (3 * d * wide + wide * d + 2 * (d * rank + rank * wide) + wide  # q, k, v; o; the two gates, one's bias
           + d * 8 + 3 * 4 * wide + 8 + wide + 128)  # w_b; three convolutions of 4 taps; A_log, dt_bias; the head norm
    gqa = d * wide + 2 * d * 128 + wide * d + d * wide  # q; k, v of one head; o; the output gate
    moe = 2 * d + d * 320 + 3 * d * f + 8 * 3 * d * f
    assert solar_open2.layer_params(config, "kda") == {"mixer": kda, "moe": moe} and kda == 18_135_176
    assert solar_open2.layer_params(config, "gqa") == {"mixer": gqa, "moe": moe} and gqa == 13_631_488
    assert moe == 142_876_672
    total = 3 * (kda + moe) + gqa + moe + 2 * 24576 * d + d
    assert solar_open2.num_params(config) == total == 840_874_392  # 840.9 M: 13.45 GB at 16 B a parameter
    assert 16 * total == pytest.approx(13.45e9, rel=1e-3)


def test_the_arithmetic_by_hand(config):
    from ray_tpu.ops import gated_delta_rule as gdn

    assert solar_open2.KDA_CHUNK == gdn.CHUNK == 128  # the floors count the chunk the kernels declare
    c, hd = 128, 128
    forward = 2 * 2 * c * hd + 3 * 2 * hd * hd + 2 * 2 * c * hd + c * c
    assert solar_open2.kda_flops_per_token(config, backward=False) == forward == 245_760
    whole = forward + 6 * 2 * c * hd + 7 * 2 * hd * hd + 5 * 2 * c * hd + c * c
    assert solar_open2.kda_flops_per_token(config) == whole == 851_968
    flops, nbytes = solar_open2.kda_flops_per_step(config, ROWS, SEQ), solar_open2.kda_bytes_per_step(config, ROWS, SEQ)
    # a token and head: 11 arrays of 128 in bf16, the vector gate and beta three times in f32, a state a chunk twice
    assert flops == whole * SEQ * 8 * 3 and nbytes == (2 * 11 * hd + 12 * (hd + 1) + 2 * 4 * hd * hd // c) * SEQ * 8 * 3
    # 0.43 ms of products against 0.65 ms of bytes a step: the bytes bind (the f32 gate is as wide as the keys)
    assert flops / 197e12 == pytest.approx(0.425e-3, rel=1e-2) and nbytes / 819e9 == pytest.approx(0.647e-3, rel=1e-2)
    d, f, wide, rank = 4096, 1280, 1024, 128
    active = (3 * (4 * d * wide + 2 * (d * rank + rank * wide)) + (3 * d * wide + 2 * d * 128)
              + 4 * (d * 320 + 3 * d * f * (1 + 8 * 8 / 320)) + 24576 * d)
    assert solar_open2.active_matmul_params(config) == pytest.approx(active, rel=1e-12)
    per_token = solar_open2.train_flops_per_token(config, SEQ)
    assert per_token == pytest.approx(6.0 * active + 12.0 * wide * SEQ + whole * 8 * 3, rel=1e-12)
    assert 6.0 * 24576 * d / per_token == pytest.approx(0.386, abs=5e-3)  # the head: two fifths of the FLOPs at this cut
    assert whole * 8 * 3 / per_token == pytest.approx(0.013, abs=2e-3)  # the scan's own products
    assert solar_open2.held_pairs_per_layer(config, SEQ) == pytest.approx(819.2)  # 102.4 a held expert
    pairs = 819.2
    assert solar_open2.moe_expert_flops_per_step(config, ROWS, SEQ) == pytest.approx(6 * 3 * d * f * pairs * 4)
    assert solar_open2.moe_expert_bytes_per_step(config, ROWS, SEQ) == pytest.approx(
        2 * 9 * (pairs * d + 8 * d * f + pairs * f) * 4)
    # the flash kernels: 8 query heads on one key/value head, one layer
    assert solar_open2.flash_flops_per_step(config, ROWS, SEQ) == 6 * SEQ * SEQ * 128 * 8
    act, stat = SEQ * 128 * 2, SEQ * 4
    assert solar_open2.flash_bytes_per_step(config, ROWS, SEQ) == 8 * (6 * act + 3 * stat) + 6 * act


def test_the_programs_own_count_agrees(config):
    from ray_tpu.models import solar_open2 as program

    cfg = solar_open2.solar_open2_config(config)
    assert program.num_params(cfg) == solar_open2.num_params(config)
    assert program.train_flops_per_token(cfg, SEQ) == pytest.approx(
        solar_open2.train_flops_per_token(config, SEQ) - solar_open2.kda_flops_per_token(config) * 8 * 3, rel=1e-12)
    assert (cfg.n_head, cfg.n_kv_head, cfg.head_dim, cfg.linear_heads, cfg.conv_kernel) == (8, 1, 128, 8, 4)
    assert (cfg.n_experts, cfg.held, cfg.first_expert_held, cfg.experts_per_token, cfg.gate_rank) == (320, 8, 0, 8, 128)
    assert cfg.layer_types == ("gqa", "kda", "kda", "kda") and cfg.allow_neg_eigval and cfg.norm_topk_prob


def test_the_held_prefix_is_a_fortieth_of_the_sort(config):
    """`moe.held_row_bound` at 8 of 320: twice the even share of the 32,768 pairs, in whole row tiles."""
    from ray_tpu.models.moe import held_row_bound

    assert held_row_bound(SEQ * 8, 8, 320) == 2048  # 1/16 of the pairs for an even share of 1/40


def test_the_attention_path_is_both_sets_of_kernels_on_the_chip():
    class Cfg:
        n_head, head_dim = 8, 128

    system = solar_open2.System.__new__(solar_open2.System)
    system.cfg = Cfg
    assert system.attention_path(1, SEQ, "tpu") == "pallas" and system.attention_path(1, SEQ, "cpu") == "xla"


def test_the_new_readers_return_nothing_on_a_program_without_the_scopes_or_the_kernels(named_run):
    """The parent's program: a traced run of it reads no `kda` scope and no `kda_*` kernel, and its line leaves the
    entries out without raising."""
    readers = Manifest().layer_readers()
    run = dict(named_run, config={"model": "solar_open2", "batch": {"global_rows": ROWS, "seq": SEQ}},
               summary={**named_run["summary"], "device": {"count": CHIPS}, "span_ms_per_step": {"data_wait": 0.25}},
               peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert [readers[name].read(run) for name in NEW] == [None] * len(NEW)
    assert readers["data.wait_ms"].read(run) == 0.25
    assert readers["moe.shared_ms"].read(run) is None  # GPT-2 has no scope `shared_expert`


def test_the_roofline_divides_the_larger_floor_by_the_kernels_time(config, monkeypatch):
    readers = Manifest().layer_readers()
    roofline = readers["kernels.kda_roofline"]
    run = {"config": config, "summary": {"device": {"count": CHIPS}},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    from types import SimpleNamespace

    from benchmark.harness import program_trace

    took = {"kda_fwd": 4.0, "kda_bwd": 9.0}
    monkeypatch.setattr(program_trace, "of", lambda run: SimpleNamespace(kernel=took.get))
    assert roofline.read(run) == pytest.approx(100 * 0.647 / 13.0, rel=1e-2)  # the bytes' floor binds
    assert readers["kernels.kda_ms"].read(run) == pytest.approx(13.0)
    assert readers["kernels.kda_fwd_ms"].read(run) == 4.0 and readers["kernels.kda_bwd_ms"].read(run) == 9.0
    assert roofline.read({**run, "peaks": None}) is None
    took.pop("kda_bwd")
    assert roofline.read(run) is None and readers["kernels.kda_ms"].read(run) is None


def test_the_reference_walks_the_tree_in_the_published_order(config):
    import jax
    import jax.numpy as jnp

    blocks = {"leading": [], "trailing": [],
              "period": [{"tag": jnp.asarray([float(place)])} for place in range(4)]}
    walked = solar_open2.layers_in_order(blocks, config)
    assert [kind for kind, _ in walked] == ["gqa", "kda", "kda", "kda"]
    assert [float(jax.tree.leaves(layer)[0]) for _, layer in walked] == [0.0, 1.0, 2.0, 3.0]
    assert set(solar_open2.LEAF_GRAD_REL_TOL) == set(solar_open2.CHECKED_LEAVES) and len(solar_open2.CHECKED_LEAVES) == 9
