"""What the SmallThinker-21BA3B configuration brings to the benchmark: its file against the catalog row, its cell and
entries appended and held to the contract, its readers on a made trace, the kernels' floors and the parameter count by
hand. A one-chip cell. Eighteen of the listed readings come as `<metric>.<configuration>` copies until a `benchmark` PR
folds them into the listed entries' own lists (`per_layer` holds 113 of the contract's 128 with this cell).
(The cell's CPU rehearsal was walked by hand, `benchmark/run.py --rehearse-cpu`: this directory's tests are run a second
time inside `test_benchmark_widening.py`.)"""

import json
import os
import sys
from types import SimpleNamespace

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import listed_readings  # noqa: E402
from benchmark.harness.manifest import Manifest, problems, reduced_problems  # noqa: E402
from benchmark.models import smallthinker  # noqa: E402
from widened_manifest import named_run  # noqa: E402,F401  (fixture)

CONFIG = "smallthinker-21b-a3b-l4"
CELL = CONFIG + ".fed16k"
ROWS, SEQ, CHIPS = 1, 16384, 1
NEW = ("attn.heads_a_program", "moe.relu_live_share")
COPIED = ("data.wait_ms", "host.h2d_ms", "host.report_ms", "host.report_put_ms", "moe.router_ms", "moe.dispatch_ms",
          "moe.experts_ms", "moe.experts_roofline", "moe.load_max_over_mean", "kernels.gmm_ms", "kernels.gmm_roofline",
          "moe.held_pairs_share", "moe.issued_over_held", "attn.window_ms", "attn.full_ms", "kernels.flash_window_ms",
          "kernels.flash_window_roofline", "swa.walked_over_live_blocks")
REDUCED = ["num_hidden_layers", "rope_layout", "sliding_window_layout", "moe_num_primary_experts", "vocab_size"]
V5E_HBM_BYTES = 16_909_336_064
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def config():
    return Manifest().config(CONFIG)


def test_the_manifest_holds_the_cell_appended_and_meets_the_contract():
    m = Manifest()
    assert problems(m) == []
    cells = [w["name"] for w in m.data["workloads"]]
    assert cells.index(CELL) >= 12 and m.cell(CELL) == {**m.cell(CELL), "config": CONFIG, "traffic": "fed16k", "chips": 1}
    entry = next(e for e in m.data["configs"] if e["name"] == CONFIG)
    assert m.data["configs"].index(entry) >= 11
    assert entry["reduced"] == REDUCED and reduced_problems(entry, m.config(CONFIG)) == []
    assert os.path.isfile(os.path.join(m.dir, "models", m.config(CONFIG)["model"] + ".py"))
    assert all(1 <= len(e["why"]) <= 200 for e in m.data["configs"] + m.data["workloads"])
    for said in ("1 x 16,384", "group 7", "4,096 window", "16 held experts see 1,536 pairs each", "1/4 of the group's 6,144",
                 "attention sees 4x", "head 1/7", "AdamW 559 M", "host"):
        assert said in m.cell(CELL)["why"], said
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024
    # One run of twenty behind the 93 entries PR 69 left: the two new readings, then the eighteen copies.
    names = [e["name"] for e in m.data["per_layer"]]
    first = names.index(NEW[0])
    assert first >= 93 and names[first:first + 2] == list(NEW)
    assert names[first + 2:first + 20] == [f"{name}.{CONFIG}" for name in COPIED] and len(names) <= 128
    assert sum(w["chips"] == 4 for w in m.data["workloads"]) >= 2 and len(cells) >= 13
    # The mix is the one that was there, unedited: rows of 16,385 out of 8-row blocks.
    assert m.traffic("fed16k") == {**m.traffic("fed16k"), "loop": "fed", "block_rows": 8, "supply_factor": 4}
    assert m.traffic("fed16k")["documents"] == {"median_tokens": 1000, "sigma": 1.6, "min_tokens": 8, "max_tokens": 32768}


def test_the_cell_reports_the_new_readings_the_copies_and_every_unlisted_one():
    by_name, unlisted = listed_readings.holds_for(CELL, [], list(NEW) + [f"{name}.{CONFIG}" for name in COPIED])
    readers = Manifest().layer_readers()
    assert len(unlisted) >= 30 and {"step.device_ms", "step.mfu_pct", "kernels.flash_ms", "kernels.flash_roofline",
                                    "device.idle_pct", "step.product_floor_ms", "step.xla_remat_ms", "compile.traces"} <= unlisted
    for name in NEW:
        assert by_name[name]["moves"] == "tokens_per_s_per_chip" and by_name[name]["source"] == "program_counter"
        assert readers[name].META == {k: v for k, v in by_name[name].items() if k != "workloads"}
    assert (by_name[NEW[0]]["unit"], by_name[NEW[0]]["better"], by_name[NEW[0]]["layer"]) == ("count", "higher", "kernels")
    assert (by_name[NEW[1]]["unit"], by_name[NEW[1]]["better"], by_name[NEW[1]]["layer"]) == ("%", "lower", "expert layer")
    for name in COPIED:  # a copy is the listed entry under the cell's name, read by the listed reader
        copy, listed = by_name[f"{name}.{CONFIG}"], by_name[name]
        assert copy == {**listed, "name": copy["name"], "workloads": [CELL]} and CELL not in listed["workloads"]
        assert listed["workloads"] == listed_readings.TABLE.get(name, listed["workloads"])
        assert readers[copy["name"]].read.__code__.co_filename == readers[name].__file__  # `read = listed.read`
        assert readers[copy["name"]].META == {k: v for k, v in copy.items() if k != "workloads"}
    m = Manifest()
    assert [e["name"] for e in m.metrics_for(CELL, "end_to_end")] == ["tokens_per_s_per_chip", "setup_s"]
    assert 113 <= len(m.data["per_layer"]) <= 128


def test_the_file_holds_every_published_key_and_cuts_counts_and_no_width(config):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        published = next(json.loads(line) for line in fh if json.loads(line)["name"] == "SmallThinker-21BA3B-Instruct")
    differ = {k for k, v in published["config"].items() if config.get(k, "missing") != v}
    assert differ == set(REDUCED) and config["reduced"] == REDUCED
    assert config["source"] == published["source_url"]
    assert config["published"] == {k: published["config"][k] for k in REDUCED}
    assert [config[k] for k in REDUCED] == [4, [0, 1, 1, 1], [0, 1, 1, 1], 16, 18992]
    assert config["published"]["rope_layout"][:4] == config["rope_layout"]  # published layers 0-3: one whole period
    assert config["published"]["sliding_window_layout"] == config["published"]["rope_layout"] == [0, 1, 1, 1] * 13
    assert smallthinker.layer_kinds(config) == ["full", "window", "window", "window"]
    assert (config["first_expert_held"], smallthinker.router_width(config), config["moe_num_active_primary_experts"]) == (0, 64, 6)
    assert config["vocab_size"] * 8 == 151936  # an eighth of the vocabulary, the guide's floor
    for width in ("hidden_size", "head_dim", "num_attention_heads", "num_key_value_heads", "moe_ffn_hidden_size",
                  "moe_num_active_primary_experts", "sliding_window_size", "rope_theta", "rms_norm_eps", "max_position_embeddings"):
        assert config[width] == published["config"][width], width
    assert (config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"],
            config["moe_ffn_hidden_size"], config["sliding_window_size"], config["rope_theta"]) == (2560, 28, 4, 128, 768, 4096, 1500000)
    assert config["layout"] == {**config["layout"], "num_workers": 1, "tpus_per_worker": 1, "mesh": None}
    for said in ("eight-chip", "8-way", "4-way", "experts 0-15", "64 outputs", "28 query heads", "pipeline stages",
                 "published layers 0-3", "not run", "nothing stands in", "reference alike"):
        assert said in config["layout"]["deployment"], said
    assert config["batch"] == {**config["batch"], "global_rows": ROWS, "seq": SEQ}
    for said in ("1,536 pairs", "a quarter", "43.75 %", "two rows compile to a peak of 16.39 GB"):
        assert said in config["batch"]["why"], said
    assert config["remat_policy"] == "save_attn" and config["rehearse_with"] == "smallthinker-nano"
    for said in ("layers_held", "activation", "router_tap", "router", "attention", "rotary", "window", "norms",
                 "moe_num_primary_experts", "init", "optimizer", "learning_rate", "remat_policy", "token_ids"):
        assert len(config["assumed"][said]) > 40, said
    for said in ("no hidden_act key", "described_as"):
        assert said in config["assumed"]["activation"], said
    assert "not from a key of config.json" in config["assumed"]["router_tap"]
    memory = config["memory_analysis_v5e_bytes"]
    assert memory["arguments"] < memory["peak_memory"] <= 12.52e9 and memory["peak_memory"] > 0.25 * V5E_HBM_BYTES
    lo, hi = config["loss_band"]
    assert lo < 9.852 + 1.0 ** 2 / 2 < hi  # ln(18,992) and half the logits' variance at the seeded weights
    assert "check_tolerances" not in config and "check_tolerances" in Manifest().config("smallthinker-nano")


def test_the_parameter_count_by_hand(config):
    d, q, kv, f = 2560, 28 * 128, 4 * 128, 768
    attention = 2 * d * q + 2 * d * kv  # W_q, W_o; W_k, W_v
    assert attention == 20_971_520 and attention + d * 64 + 2 * d == 21_140_480  # ISSUE 70's 21.14 M outside the experts
    expert = 3 * d * f
    assert expert == 5_898_240
    assert smallthinker.layer_params(config) == {"attention": attention, "norms": 2 * d, "router": d * 64, "experts": 16 * expert}
    total = 4 * (attention + 2 * d + d * 64 + 16 * expert) + 2 * 18992 * d + d
    assert smallthinker.num_params(config) == total == 559_290_880  # 559.3 M: 8.95 GB at 16 B a parameter
    assert 16 * total == pytest.approx(8.95e9, rel=1e-3)


def test_the_arithmetic_by_hand(config):
    band = 4096 * 4097 // 2 + (SEQ - 4096) * 4096
    triangle = SEQ * (SEQ + 1) // 2
    assert (smallthinker.kept_pairs(config, SEQ, "window"), smallthinker.kept_pairs(config, SEQ, "full")) == (band, triangle)
    assert band == 58_722_304 and triangle == 134_225_920 and band / triangle == pytest.approx(0.4375, abs=1e-4)
    assert smallthinker.kept_pairs(config, 1024, "window") == 1024 * 1025 // 2  # a row inside the window: the triangle
    d, f = 2560, 768
    active = 4 * (20_971_520 + d * 64 + 3 * d * f * 6 * 16 / 64) + 18992 * d
    assert smallthinker.active_matmul_params(config) == pytest.approx(active, rel=1e-12) and active == pytest.approx(168.55e6, rel=1e-4)
    per_token = smallthinker.train_flops_per_token(config, SEQ)
    assert per_token == pytest.approx(6.0 * active + 12.0 * 28 * 128 * (3 * band + triangle) / SEQ, rel=1e-12)
    flash_share = 12.0 * 28 * 128 * (3 * band + triangle) / SEQ / per_token
    assert flash_share == pytest.approx(0.446, abs=2e-3)  # ISSUE 70's "about 45 % of the step's FLOPs"
    assert 6 * 18992 * d / per_token == pytest.approx(1 / 6.26, rel=2e-2)  # the head: ~1/7 of the step's FLOPs with attention's
    assert smallthinker.held_pairs_per_layer(config, SEQ) == 24576  # 1,536 a held expert
    assert smallthinker.moe_expert_flops_per_step(config, ROWS, SEQ) == pytest.approx(6 * 3 * d * f * 24576 * 4)
    assert smallthinker.moe_expert_bytes_per_step(config, ROWS, SEQ) == pytest.approx(2 * 9 * (24576 * d + 16 * d * f + 24576 * f) * 4)
    # the flash kernels: three band calls and one triangle, 28 query heads on 4 key/value heads, counted from the kept scores
    assert smallthinker.flash_flops_per_step(config, ROWS, SEQ) == 12 * 128 * (3 * band + triangle) * 28
    assert smallthinker.flash_window_flops_per_step(config, ROWS, SEQ) == 12 * 128 * 3 * band * 28
    assert smallthinker.flash_window_flops_per_step(config, ROWS, SEQ) / smallthinker.flash_flops_per_step(config, ROWS, SEQ) == (
        pytest.approx(0.568, abs=1e-3))
    act, stat = SEQ * 128 * 2, SEQ * 4
    one_call = 28 * (6 * act + 3 * stat) + 4 * 6 * act
    assert smallthinker.flash_bytes_per_step(config, ROWS, SEQ) == 4 * one_call
    assert smallthinker.flash_window_bytes_per_step(config, ROWS, SEQ) == 3 * one_call
    # the products bind in both: 38.5 ms of band and 67.8 ms in all against 2.4 and 3.2 ms of bytes
    assert smallthinker.flash_window_flops_per_step(config, ROWS, SEQ) / 197e12 == pytest.approx(38.46e-3, rel=1e-2)
    assert smallthinker.flash_flops_per_step(config, ROWS, SEQ) / 197e12 == pytest.approx(67.76e-3, rel=1e-2)
    assert smallthinker.flash_bytes_per_step(config, ROWS, SEQ) / 819e9 < 4e-3


def test_the_programs_own_count_agrees(config):
    from ray_tpu.models import smallthinker as program

    cfg = smallthinker.smallthinker_config(config)
    assert program.num_params(cfg) == smallthinker.num_params(config)
    assert program.train_flops_per_token(cfg, SEQ) == pytest.approx(smallthinker.train_flops_per_token(config, SEQ), rel=1e-12)
    assert program.kept_pairs(SEQ, 4096) == smallthinker.kept_pairs(config, SEQ, "window")
    assert (cfg.n_head, cfg.n_kv_head, cfg.head_dim, cfg.d_model, cfg.d_expert) == (28, 4, 128, 2560, 768)
    assert (cfg.n_experts, cfg.held, cfg.first_expert_held, cfg.experts_per_token) == (64, 16, 0, 6)
    assert cfg.kinds == ("full", "window", "window", "window")
    assert (cfg.sliding_window, cfg.rope_theta, cfg.norm_eps) == (4096, 1.5e6, 1e-6)


def test_the_held_prefix_is_half_of_the_sort(config):
    """`moe.held_row_bound` at 16 of 64: twice the even share of the 98,304 pairs, in whole row tiles."""
    from ray_tpu.models.moe import held_row_bound

    assert held_row_bound(SEQ * 6, 16, 64) == 49152


def test_the_attention_path_is_the_kernels_on_the_chip():
    system = smallthinker.System.__new__(smallthinker.System)
    system.cfg = SimpleNamespace(n_head=28, head_dim=128)
    assert system.attention_path(1, SEQ, "tpu") == "pallas" and system.attention_path(1, SEQ, "cpu") == "xla"


def test_the_new_readers_return_nothing_on_a_program_without_what_they_read(named_run):
    """The parent's program: a traced run of it carries no `group_<n>` scope and its check counts no live share, and
    its line leaves the entries out without raising."""
    readers = Manifest().layer_readers()
    run = dict(named_run, config={"model": "smallthinker", "batch": {"global_rows": ROWS, "seq": SEQ}},
               summary={**named_run["summary"], "device": {"count": CHIPS}, "span_ms_per_step": {"data_wait": 0.25}},
               peaks=PEAKS)
    assert [readers[name].read(run) for name in NEW] == [None] * len(NEW)
    untraced = dict(run, device_trace=None)
    untraced.pop("program_trace", None)
    assert [readers[name].read(untraced) for name in NEW] == [None] * len(NEW)
    assert readers[NEW[1]].read({"summary": {"check": {"routing": {"relu_live_share": 0.4999}}}}) == pytest.approx(49.99)
    assert readers[NEW[1]].read({"summary": {"check": {}}}) is None


def _program(ops, scopes, steps):
    """A `ProgramTrace` of one device: `ops` [name, start, dur] all Mosaic calls, `steps` [(start, dur)]."""
    from benchmark.harness import xplane

    dev = {"ops": [[name, "custom-call", xplane.MOSAIC_TARGET, 0, start, dur] for name, start, dur in ops]}
    trace = SimpleNamespace(devices=[dev], _leaf_ops=lambda d: d["ops"],
                            step_runs=lambda d: [(0, 0, start, dur) for start, dur in steps])
    trace.per_step = lambda d, pick: [
        xplane.measure(xplane.clip(((op[4], op[4] + op[5]) for op in d["ops"] if pick(op)), start, start + dur))
        for start, dur in steps]
    return SimpleNamespace(trace=trace, scopes=scopes)


def test_the_readers_pick_the_plan_the_window_calls_and_the_band(config, monkeypatch):
    from benchmark.harness import program_trace

    readers = Manifest().layer_readers()
    path = "jit(step_fn)/{phase}(blocks)/layer_scan/while/body/closed_call/attention/{kind}/{walk}/{kernel}/pallas_call"
    window_walk, full_walk = "tiles_140of512/keys_1008of1120", "tiles_272of512/keys_2112of2176"
    scopes = {
        "fwd_w": path.format(phase="jvp", kind="window", walk=window_walk, kernel="group_7/flash_fwd"),
        "bwd_w": path.format(phase="transpose(jvp", kind="window", walk=window_walk, kernel="flash_bwd"),
        "fwd_f": path.format(phase="jvp", kind="full", walk=full_walk, kernel="group_14/flash_fwd"),
        "bwd_f": path.format(phase="transpose(jvp", kind="full", walk=full_walk, kernel="flash_bwd"),
        "gmm": "jit(step_fn)/jvp(blocks)/layer_scan/while/body/closed_call/window/moe/experts/gmm_fwd/pallas_call",
    }
    ms = 1_000_000
    ops = [("fwd_w", 0, 20 * ms), ("bwd_w", 25 * ms, 43 * ms), ("fwd_f", 70 * ms, 13 * ms), ("bwd_f", 85 * ms, 29 * ms),
           ("gmm", 120 * ms, 2 * ms)]
    program = _program(ops, scopes, [(0, 130 * ms)])
    monkeypatch.setattr(program_trace, "of", lambda run: program)
    run = {"config": config, "summary": {"device": {"count": CHIPS}}, "peaks": PEAKS}
    assert readers["attn.heads_a_program"].read(run) == 7  # the least over the traced forward calls, not the experts' kernels
    copy = lambda name: readers[f"{name}.{CONFIG}"]  # noqa: E731
    assert copy("kernels.flash_window_ms").read(run) == pytest.approx(63.0)
    assert copy("attn.window_ms").read(run) == pytest.approx(63.0) and copy("attn.full_ms").read(run) == pytest.approx(42.0)
    assert copy("swa.walked_over_live_blocks").read(run) == pytest.approx(1120 / 1008)
    band_ms = 12 * 128 * 3 * 58_722_304 * 28 / 197e12 * 1e3
    assert copy("kernels.flash_window_roofline").read(run) == pytest.approx(100 * band_ms / 63.0, rel=1e-9)
    # a program whose forward kernels carry no `group_<n>` (whole heads a program): nothing
    program.scopes = {name: scope.replace("group_7/", "").replace("group_14/", "") for name, scope in scopes.items()}
    assert readers["attn.heads_a_program"].read(run) is None


def test_the_reference_walks_the_tree_in_the_published_order(config):
    import jax
    import jax.numpy as jnp

    blocks = {"leading": [], "trailing": [], "period": [{"tag": jnp.asarray([float(place)])} for place in range(4)]}
    walked = smallthinker.layers_in_order(blocks, config)
    assert [kind for kind, _ in walked] == ["full", "window", "window", "window"]
    assert [float(jax.tree.leaves(layer)[0]) for _, layer in walked] == [0.0, 1.0, 2.0, 3.0]
    assert set(smallthinker.LEAF_GRAD_REL_TOL) == set(smallthinker.CHECKED_LEAVES) and len(smallthinker.CHECKED_LEAVES) == 8
    source = open(smallthinker.__file__).read()
    reference = source[source.index("def expert_layer"):source.index("# Tolerances of the agreement")]
    assert "ray_tpu" not in reference  # written from the description: nothing of the program's models or kernels
