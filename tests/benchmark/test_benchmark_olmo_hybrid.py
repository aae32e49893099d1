"""What the Olmo-Hybrid configuration brings to the benchmark: its file against the catalog row, its cell and entries
appended and held to the contract, its readers on a recorded trace, the floors' arithmetic by hand. The cell is the second
of four chips, which `max(1, 9 // 4)` lets in. It brought the eight listed readings it reports as `<metric>.<config>`
copies (PR 51); PR 56 put it on those entries' own lists and deleted the copies (`listed_readings.TABLE`).
(The cell's CPU rehearsal is `tests/test_olmo_hybrid_rehearsal.py`: it costs a minute and a half, and this directory's
tests are run a second time inside `test_benchmark_widening.py`.)"""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import listed_readings  # noqa: E402
from benchmark.harness.manifest import Manifest, problems, reduced_problems  # noqa: E402
from benchmark.models import olmo_hybrid  # noqa: E402
from widened_manifest import named_run, rehearsals_own, room, widen  # noqa: E402,F401  (fixture)

CONFIG = "olmo-hybrid-7b-fsdp4"
CELL = CONFIG + ".fed4k"
ROWS, SEQ, CHIPS = 4, 4096, 4
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
NEW = ("gdn.mixer_ms", "gdn.conv_ms", "gdn.gates_ms", "kernels.gdn_fwd_ms", "kernels.gdn_bwd_ms", "kernels.gdn_ms",
       "kernels.gdn_roofline")
# The listed readings a four-worker fed cell with a dense SwiGLU reports (the ranks' least exposed time since PR 56). Not `data.fetch_block_ms`: a worker eats a
# row a step and a block holds 16, so a pull falls into one window of 8 steps in two. Not `host.stall_pct`: 34 steps
# a window leave no three readings a position clear of the traced ones.
LISTED = ("data.wait_ms", "host.h2d_ms", "host.report_ms", "host.report_put_ms", "collectives.total_ms",
          "collectives.exposed_ms", "collectives.exposed_min_ms", "entry.gang_join_s", "step.dense_mlp_ms")


@pytest.fixture(scope="module")
def config():
    return Manifest().config(CONFIG)


def test_the_manifest_holds_the_cell_appended_and_meets_the_contract():
    m = Manifest()
    assert problems(m) == []
    # Appended at the end of each list: the ninth cell (a later one may follow it), the second of four chips.
    cells = [w["name"] for w in m.data["workloads"]]
    assert cells[8] == CELL and m.cell(CELL) == {**m.cell(CELL), "config": CONFIG, "traffic": "fed4k", "chips": 4}
    # `max(1, 9 // 4)` = 2 lets the second four-chip cell in.
    assert [w["name"] for w in m.data["workloads"] if w["chips"] == 4][:2] == ["gpt2-xl-fsdp4.fed", CELL]
    entry = m.data["configs"][7]  # the eighth configuration: `gpt2-medium` has two of the cells
    assert entry["name"] == CONFIG and reduced_problems(entry, m.config(CONFIG)) == []
    assert os.path.isfile(os.path.join(m.dir, "models", m.config(CONFIG)["model"] + ".py"))
    assert all(1 <= len(e["why"]) <= 200 for e in m.data["configs"] + m.data["workloads"])
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024
    # One run of seven after the 59 entries PR 50 left: the new readings (the eight copies that followed went in PR 56).
    names = [e["name"] for e in m.data["per_layer"]]
    assert names[59:66] == list(NEW) and not [name for name in names if name.endswith("." + CONFIG)]


def test_the_cell_reports_the_new_readings_each_listed_one_and_every_unlisted_one():
    m = Manifest()
    readers = m.layer_readers()
    by_name, _ = listed_readings.holds_for(CELL, LISTED, NEW)
    for name in NEW:
        assert by_name[name]["moves"] == "tokens_per_s_per_chip"
        assert readers[name].META == {k: v for k, v in by_name[name].items() if k != "workloads"}
    assert by_name["kernels.gdn_roofline"]["unit"] == "%" and by_name["kernels.gdn_roofline"]["better"] == "higher"
    assert {by_name[n]["layer"] for n in NEW[:3]} == {"linear attention"} and by_name[NEW[3]]["layer"] == "kernels"
    # Every end-to-end metric the cell reports is one the benchmark has, under its bound.
    assert [e["name"] for e in m.metrics_for(CELL, "end_to_end")] == ["tokens_per_s_per_chip", "setup_s"]


def test_the_manifest_can_be_widened_twice_more_under_the_cap(tmp_path):
    """Two widenings meet the contract and add what they say, and `widened_manifest.room` counts none of it: a
    rehearsal's own entries, in the tree this starts from too, are nobody's readings, so the copy widened twice has the
    room this tree has (the cap is on the live manifest, and no copy is held to it: PR 69). The bound on that count is
    `test_benchmark_manifest.py`'s to hold, once."""
    once = widen(str(tmp_path / "once"))
    twice = widen(str(tmp_path / "twice"), base=once.root)
    m, start = Manifest(twice.root), Manifest().data["per_layer"]
    assert problems(m) == [] and len(once.metrics) == len(twice.metrics)
    assert len(m.data["per_layer"]) == len(start) + 2 * len(once.metrics)
    assert [e["name"] for e in rehearsals_own(m.data["per_layer"])][-2 * len(once.metrics):] == once.metrics + twice.metrics
    assert room(m.data["per_layer"]) == room(start)


def test_the_file_holds_every_published_key_and_cuts_the_depth_alone(config):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        published = next(json.loads(line) for line in fh if json.loads(line)["name"] == "Olmo-Hybrid-7B")
    differ = {k for k, v in published["config"].items() if config.get(k, "missing") != v}
    assert differ == {"num_hidden_layers", "layer_types"} and config["reduced"] == ["num_hidden_layers", "layer_types"]
    assert config["source"] == published["source_url"]
    assert config["published"] == {"num_hidden_layers": 32, "layer_types": PERIOD * 8}
    assert config["num_hidden_layers"] == 8 == len(config["layer_types"]) and config["layer_types"] == PERIOD * 2
    assert config["vocab_size"] == 100352  # the whole vocabulary
    assert config["layout"] == {**config["layout"], "num_workers": 4, "tpus_per_worker": 1, "mesh": {"fsdp": 4}}
    assert "fsdp=4" in config["layout"]["deployment"] and "pipeline stages" in config["layout"]["deployment"]
    assert config["batch"] == {**config["batch"], "global_rows": ROWS, "seq": SEQ} and len(config["batch"]["why"]) > 40
    assert config["remat_policy"] == "save_attn" and config["rehearse_with"] == "olmo-hybrid-nano"
    for said in ("block", "linear_attention", "full_attention", "rope", "gates_init", "init", "documents", "optimizer",
                 "learning_rate", "layout", "loss_band"):
        assert len(config["assumed"][said]) > 40, said
    memory = config["memory_analysis_v5e_bytes"]
    assert memory["arguments"] < memory["peak"] < 16_909_336_064 and memory["peak"] > 0.25 * 16_909_336_064
    lo, hi = config["loss_band"]
    assert lo < 11.52 + 1.24 ** 2 / 2 < hi  # ln(100,352) and half the logits' variance at the seeded weights
    assert "check_tolerances" not in config and "check_tolerances" in Manifest().config("olmo-hybrid-nano")


def test_the_arithmetic_by_hand(config):
    from ray_tpu.ops import gated_delta_rule as gdn

    assert olmo_hybrid.GDN_CHUNK == gdn.CHUNK == 128  # the floors count the chunk the kernels declare
    d, keys, values, ff, vocab = 3840, 30 * 96, 30 * 192, 11008, 100352
    linear, full = d * (2 * keys + 3 * values) + 3 * d * ff, 4 * d * d + 3 * d * ff
    assert olmo_hybrid.matmul_params(config) == 6 * linear + 2 * full + vocab * d == 2_048_655_360
    # A token and head of the scan: forward 2 x 2Cd_k + 3 x 2d_kd_v + 2 x 2Cd_v + C^2, the backward pass 6, 7 and 5 more.
    c, dk, dv = 128, 96, 192
    forward = 2 * 2 * c * dk + 3 * 2 * dk * dv + 2 * 2 * c * dv + c * c
    assert olmo_hybrid.gdn_flops_per_token(config, backward=False) == forward == 274_432
    assert olmo_hybrid.gdn_flops_per_token(config) == forward + 6 * 2 * c * dk + 7 * 2 * dk * dv + 5 * 2 * c * dv + c * c == 942_080
    assert olmo_hybrid.gdn_flops_per_token(config, chunk=64) == 647_168  # ISSUE 51's count, at 64 positions a chunk
    rows = ROWS // CHIPS
    flops, nbytes = olmo_hybrid.gdn_flops_per_step(config, rows, SEQ), olmo_hybrid.gdn_bytes_per_step(config, rows, SEQ)
    assert flops == 942_080 * SEQ * 30 * 6 and nbytes == (2 * (4 * dk + 4 * dv + 2 * dk + dv) + 24) * SEQ * 30 * 6
    # 3.53 ms of products against 2.79 ms of bytes a step a chip: at 128 positions a chunk the products bind.
    assert flops / 197e12 == pytest.approx(3.526e-3, rel=1e-3) and nbytes / 819e9 == pytest.approx(2.787e-3, rel=1e-3)
    per_token = olmo_hybrid.train_flops_per_token(config, SEQ)
    assert per_token == 6.0 * 2_048_655_360 + 12.0 * 2 * d * SEQ + 942_080 * 30 * 6
    assert 12.0 * 2 * d * SEQ / per_token == pytest.approx(0.0296, abs=1e-3)  # full-square attention in two layers
    assert 6.0 * vocab * d / per_token == pytest.approx(0.181, abs=2e-3)  # the head, whole: 5 % at full depth
    as_olmoe = {"n_embd": d, "n_head": 30, "n_layer": 2}
    from benchmark.models import gpt2
    assert olmo_hybrid.flash_flops_per_step(config, rows, SEQ) == gpt2.flash_flops_per_step(as_olmoe, rows, SEQ)
    assert olmo_hybrid.flash_bytes_per_step(config, rows, SEQ) == gpt2.flash_bytes_per_step(as_olmoe, rows, SEQ)


def test_the_programs_own_count_agrees(config):
    from ray_tpu.models import olmo_hybrid as program

    cfg = olmo_hybrid.olmo_hybrid_config(config)
    assert program.train_flops_per_token(cfg, SEQ) == pytest.approx(
        olmo_hybrid.train_flops_per_token(config, SEQ) - olmo_hybrid.gdn_flops_per_token(config) * 30 * 6, rel=1e-12)
    assert (cfg.n_head, cfg.head_dim, cfg.linear_heads, cfg.conv_kernel, cfg.allow_neg_eigval) == (30, 128, 30, 4, True)


def test_the_attention_path_is_both_sets_of_kernels_on_the_chip(config):
    class Cfg:
        n_head, head_dim = 30, 128

    system = olmo_hybrid.System.__new__(olmo_hybrid.System)
    system.cfg = Cfg
    assert system.attention_path(1, SEQ, "tpu") == "pallas" and system.attention_path(1, SEQ, "cpu") == "xla"


def test_the_new_readers_return_nothing_on_a_program_without_the_scopes_or_the_kernels(named_run):
    """The parent's program: a traced run of it reads no `gdn` scope and no `gdn_*` kernel, and its line leaves the
    entries out without raising."""
    readers = Manifest().layer_readers()
    run = dict(named_run, config={"model": "olmo_hybrid", "batch": {"global_rows": ROWS, "seq": SEQ}},
               summary={**named_run["summary"], "device": {"count": CHIPS}, "span_ms_per_step": {"data_wait": 0.25}},
               peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert [readers[name].read(run) for name in NEW] == [None] * len(NEW)
    assert readers["data.wait_ms"].read(run) == 0.25
    assert readers["step.dense_mlp_ms"].read(run) is None  # GPT-2 has no scope `dense_mlp`


def test_the_roofline_divides_the_larger_floor_by_the_kernels_time(config, monkeypatch):
    readers = Manifest().layer_readers()
    roofline = readers["kernels.gdn_roofline"]
    run = {"config": config, "summary": {"device": {"count": CHIPS}},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    from types import SimpleNamespace

    from benchmark.harness import program_trace

    took = {"gdn_fwd": 29.5, "gdn_bwd": 42.8}
    monkeypatch.setattr(program_trace, "of", lambda run: SimpleNamespace(kernel=took.get))
    assert roofline.read(run) == pytest.approx(100 * 3.526 / 72.3, rel=1e-3)  # six layers at 12.05 ms: 4.9 %
    assert readers["kernels.gdn_ms"].read(run) == pytest.approx(72.3)
    assert roofline.read({**run, "peaks": None}) is None
    took.pop("gdn_bwd")
    assert roofline.read(run) is None and readers["kernels.gdn_ms"].read(run) is None
