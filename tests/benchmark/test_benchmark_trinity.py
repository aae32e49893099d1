"""What the Trinity-Mini configuration brings to the benchmark: its file against the catalog row, its cell and entries
appended and held to the contract, its readers on a recorded trace, the kernels' floors and the parameter count by hand.
A one-chip cell (both four-chip slots are taken). It brought seven of the listed readings it reports as
`<metric>.<configuration>` copies (PR 61); PR 65 put it on those entries' own lists, deleted the copies, and put it on
the lists of the eight more that it reads in every traced run (`listed_readings.TABLE`).
(The cell's CPU rehearsal is `tests/test_trinity_rehearsal.py`: this directory's tests are run a second time inside
`test_benchmark_widening.py`.)"""

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
from benchmark.models import trinity  # noqa: E402
from widened_manifest import named_run  # noqa: E402,F401  (fixture)

CONFIG = "trinity-mini-ep16-l5"
CELL = CONFIG + ".fed16k"
ROWS, SEQ, CHIPS = 1, 16384, 1
NEW = ("attn.window_ms", "attn.full_ms", "kernels.flash_window_ms", "kernels.flash_window_roofline",
       "swa.walked_over_live_blocks")
# The listed readings a one-chip fed cell with a leading dense layer, experts and a shared one reports in every traced
# run: the seven it brought as copies, then the eight it joined at PR 65 (`JOINED`: three traced runs of the cell on
# the chip read each, `benchmark/testdata/trinity_traced_lines.json`). Not `data.fetch_block_ms` and not
# `host.stall_pct`: 8-row blocks last 8 steps of a row, and a window holds about 44 steps.
BROUGHT = ("moe.experts_ms", "moe.experts_roofline", "moe.load_max_over_mean", "kernels.gmm_ms", "kernels.gmm_roofline",
           "moe.shared_ms", "step.dense_mlp_ms")
JOINED = ("data.wait_ms", "host.h2d_ms", "host.report_ms", "host.report_put_ms", "moe.router_ms", "moe.dispatch_ms",
          "moe.held_pairs_share", "moe.issued_over_held")
LISTED = BROUGHT + JOINED
REDUCED = ["num_hidden_layers", "num_dense_layers", "layer_types", "num_experts", "vocab_size"]
V5E_HBM_BYTES = 16_909_336_064
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def config():
    return Manifest().config(CONFIG)


def test_the_manifest_holds_the_cell_appended_and_meets_the_contract():
    m = Manifest()
    assert problems(m) == []
    cells = [w["name"] for w in m.data["workloads"]]
    assert cells[10] == CELL and m.cell(CELL) == {**m.cell(CELL), "config": CONFIG, "traffic": "fed16k", "chips": 1}
    entry = m.data["configs"][9]
    assert entry["name"] == CONFIG and entry["reduced"] == REDUCED and reduced_problems(entry, m.config(CONFIG)) == []
    assert os.path.isfile(os.path.join(m.dir, "models", m.config(CONFIG)["model"] + ".py"))
    assert all(1 <= len(e["why"]) <= 200 for e in m.data["configs"] + m.data["workloads"])
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024
    # One run of five after the 81 entries PR 60 left less Solar-Open2's fourteen copies: the new readings (the seven
    # copies that followed went in PR 65).
    names = [e["name"] for e in m.data["per_layer"]]
    assert names[81:86] == list(NEW) and not [name for name in names if name.endswith("." + CONFIG)]
    # The mix is the one that was there, unedited: rows of 16,385 out of 8-row blocks.
    assert m.traffic("fed16k") == {**m.traffic("fed16k"), "loop": "fed", "block_rows": 8, "supply_factor": 4}
    assert m.traffic("fed16k")["documents"] == {"median_tokens": 1000, "sigma": 1.6, "min_tokens": 8, "max_tokens": 32768}


def test_the_cell_reports_the_new_readings_each_listed_one_and_every_unlisted_one():
    m = Manifest()
    by_name, unlisted = listed_readings.holds_for(CELL, LISTED, NEW)
    readers = m.layer_readers()
    assert len(unlisted) >= 30 and {"step.mfu_pct", "kernels.flash_ms", "kernels.flash_roofline", "step.product_floor_ms",
                                    "step.xla_remat_ms", "compile.traces"} <= unlisted
    for name in NEW:
        assert by_name[name]["moves"] == "tokens_per_s_per_chip"
        assert readers[name].META == {k: v for k, v in by_name[name].items() if k != "workloads"}
    assert by_name["kernels.flash_window_roofline"]["unit"] == "%" and by_name["kernels.flash_window_roofline"]["better"] == "higher"
    assert {by_name[n]["layer"] for n in (NEW[0], NEW[1], NEW[4])} == {"window attention"}
    assert by_name[NEW[2]]["layer"] == by_name[NEW[3]]["layer"] == "kernels"
    assert [e["name"] for e in m.metrics_for(CELL, "end_to_end")] == ["tokens_per_s_per_chip", "setup_s"]


@pytest.mark.parametrize("name", JOINED)
def test_a_reading_the_cell_joined_reads_a_number_in_every_recorded_traced_run(name):
    """An entry lists a cell only where its reader returns a value in every traced run of that cell (a listed reading
    that comes back `null` refuses the next `benchmark` PR): the lines of three traced runs of this cell on the chip,
    and what the span and counter readers read there, through the readers as they stand."""
    with open(os.path.join(REPO, "benchmark", "testdata", "trinity_traced_lines.json")) as fh:
        recorded = json.load(fh)
    m = Manifest()
    reader, mine = m.layer_readers()[name], [e["name"] for e in m.metrics_for(CELL, "per_layer") if "workloads" in e]
    assert recorded["cell"] == CELL and name in mine and len({run["seed"] for run in recorded["runs"]}) >= 3
    for run in recorded["runs"]:
        line = run["metrics"]
        assert run["correct"] is True and run["device"]["platform"] == "tpu"
        assert isinstance(line[name], float) and 0 < line[name] < float("inf")
        assert set(mine) <= set(line) and not [n for n in line if n.endswith("." + CONFIG)]
        if name not in ("host.report_put_ms", "moe.router_ms", "moe.dispatch_ms"):  # those three read the raw trace
            assert reader.read({"summary": run["summary"], "device_trace": None}) == line[name]


def test_the_file_holds_every_published_key_and_cuts_counts_and_no_width(config):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        published = next(json.loads(line) for line in fh if json.loads(line)["name"] == "Trinity-Mini")
    differ = {k for k, v in published["config"].items() if config.get(k, "missing") != v}
    assert differ == set(REDUCED) and config["reduced"] == REDUCED
    assert config["source"] == published["source_url"]
    assert config["published"] == {k: published["config"][k] for k in REDUCED}
    assert (config["num_hidden_layers"], config["num_dense_layers"]) == (5, 1)
    assert config["layer_types"] == ["sliding_attention"] * 4 + ["full_attention"]
    # published layers 1, 4, 5, 6, 7: a leading dense layer and one whole period in the published order
    assert [config["published"]["layer_types"][i] for i in (1, 4, 5, 6, 7)] == config["layer_types"]
    assert trinity.layer_kinds(config) == ["dense_window", "window", "window", "window", "full"]
    assert (config["num_experts"], config["first_expert_held"], trinity.router_width(config)) == (8, 0, 128)
    assert config["vocab_size"] * 8 == 200192  # an eighth of the vocabulary, the guide's floor
    for width in ("hidden_size", "head_dim", "num_attention_heads", "num_key_value_heads", "intermediate_size",
                  "moe_intermediate_size", "num_experts_per_tok", "num_shared_experts", "sliding_window", "route_scale"):
        assert config[width] == published["config"][width]
    assert (config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"],
            config["intermediate_size"], config["moe_intermediate_size"], config["sliding_window"]) == (
        2048, 32, 4, 128, 6144, 1024, 2048)
    assert config["layout"] == {**config["layout"], "num_workers": 1, "tpus_per_worker": 1, "mesh": None}
    for said in ("sixteen-chip", "16-way", "8-way", "pipeline stages", "120 experts", "reference alike", "this chip's rows"):
        assert said in config["layout"]["deployment"], said
    assert config["batch"] == {**config["batch"], "global_rows": ROWS, "seq": SEQ}
    for said in ("1,024 pairs", "a sixteenth", "23.4 %", "two rows do not fit"):
        assert said in config["batch"]["why"], said
    assert config["remat_policy"] == "save_attn" and config["rehearse_with"] == "trinity-nano"
    for said in ("layers_held", "sandwich_norms", "attention_gate", "qk_norm", "rotary", "window", "embedding_scale", "router",
                 "expert_bias", "aux_loss", "shared_expert", "num_experts", "init", "optimizer", "learning_rate"):
        assert len(config["assumed"][said]) > 40, said
    memory = config["memory_analysis_v5e_bytes"]
    assert memory["arguments"] < memory["peak_memory"] <= 15.5e9 and memory["peak_memory"] > 0.25 * V5E_HBM_BYTES
    lo, hi = config["loss_band"]
    assert lo < 10.13 + 0.905 ** 2 / 2 < hi  # ln(25,024) and half the logits' variance at the seeded weights
    assert "check_tolerances" not in config and "check_tolerances" in Manifest().config("trinity-nano")


def test_the_parameter_count_by_hand(config):
    d, q, kv, f = 2048, 32 * 128, 4 * 128, 1024
    attention = 3 * d * q + 2 * d * kv + 2 * 128  # W_q, W_g, W_o; W_k, W_v; two head norms
    assert attention == 27_263_232
    dense = 3 * d * 6144
    moe = d * 128 + 128 + 3 * d * f + 8 * 3 * d * f  # the router whole, its bias, the shared expert whole, 8 of 128 experts
    assert (dense, moe) == (37_748_736, 56_885_376)
    assert trinity.layer_params(config, True) == {"attention": attention, "norms": 4 * d, "ff": dense}
    assert trinity.layer_params(config, False) == {"attention": attention, "norms": 4 * d, "ff": moe}
    total = (attention + 4 * d + dense) + 4 * (attention + 4 * d + moe) + 2 * 25024 * d + d
    assert trinity.num_params(config) == total == 504_147_712  # 504.15 M: 8.07 GB at 16 B a parameter
    assert 16 * total == pytest.approx(8.07e9, rel=1e-3)


def test_the_arithmetic_by_hand(config):
    band = 2048 * 2049 // 2 + (SEQ - 2048) * 2048
    triangle = SEQ * (SEQ + 1) // 2
    assert (trinity.kept_pairs(config, SEQ, "window"), trinity.kept_pairs(config, SEQ, "full")) == (band, triangle)
    assert trinity.kept_pairs(config, SEQ, "dense_window") == band == 31_458_304 and triangle == 134_225_920
    assert trinity.kept_pairs(config, 1024, "window") == 1024 * 1025 // 2  # a row inside the window: the triangle
    d, q, kv, f = 2048, 4096, 512, 1024
    attention = 3 * d * q + 2 * d * kv
    active = 5 * attention + 3 * d * 6144 + 4 * (d * 128 + 3 * d * f * (1 + 8 * 8 / 128)) + 25024 * d
    assert trinity.active_matmul_params(config) == pytest.approx(active, rel=1e-12)
    per_token = trinity.train_flops_per_token(config, SEQ)
    assert per_token == pytest.approx(6.0 * active + 12.0 * 32 * 128 * (4 * band + triangle) / SEQ, rel=1e-12)
    assert 12.0 * 32 * 128 * (4 * band + triangle) / SEQ / per_token == pytest.approx(0.330, abs=5e-3)  # attention: a third
    assert trinity.held_pairs_per_layer(config, SEQ) == 8192  # 1,024 a held expert
    assert trinity.moe_expert_flops_per_step(config, ROWS, SEQ) == pytest.approx(6 * 3 * d * f * 8192 * 4)
    assert trinity.moe_expert_bytes_per_step(config, ROWS, SEQ) == pytest.approx(2 * 9 * (8192 * d + 8 * d * f + 8192 * f) * 4)
    # the flash kernels: four band calls and one triangle, 32 query heads on 4 key/value heads, counted from the kept scores
    assert trinity.flash_flops_per_step(config, ROWS, SEQ) == 12 * 128 * (4 * band + triangle) * 32
    assert trinity.flash_window_flops_per_step(config, ROWS, SEQ) == 12 * 128 * 4 * band * 32
    act, stat = SEQ * 128 * 2, SEQ * 4
    one_call = 32 * (6 * act + 3 * stat) + 4 * 6 * act
    assert trinity.flash_bytes_per_step(config, ROWS, SEQ) == 5 * one_call
    assert trinity.flash_window_bytes_per_step(config, ROWS, SEQ) == 4 * one_call
    # the products bind in both: 6.2 ms of band and 12.8 ms in all against 0.9 and 1.1 ms of bytes
    assert trinity.flash_window_flops_per_step(config, ROWS, SEQ) / 197e12 == pytest.approx(31.4e-3, rel=1e-2)
    assert trinity.flash_flops_per_step(config, ROWS, SEQ) / 197e12 == pytest.approx(64.9e-3, rel=1e-2)
    assert trinity.flash_bytes_per_step(config, ROWS, SEQ) / 819e9 < 6e-3


def test_the_programs_own_count_agrees(config):
    from ray_tpu.models import trinity as program

    cfg = trinity.trinity_config(config)
    assert program.num_params(cfg) == trinity.num_params(config)
    assert program.train_flops_per_token(cfg, SEQ) == pytest.approx(trinity.train_flops_per_token(config, SEQ), rel=1e-12)
    assert program.kept_pairs(SEQ, 2048) == trinity.kept_pairs(config, SEQ, "window")
    assert (cfg.n_head, cfg.n_kv_head, cfg.head_dim, cfg.d_ff, cfg.d_expert) == (32, 4, 128, 6144, 1024)
    assert (cfg.n_experts, cfg.held, cfg.first_expert_held, cfg.experts_per_token, cfg.route_scale) == (128, 8, 0, 8, 2.826)
    assert cfg.kinds == ("dense_window", "window", "window", "window", "full")
    assert (cfg.sliding_window, cfg.rope_theta, cfg.load_balance_coeff, cfg.norm_eps) == (2048, 10000.0, 0.001, 1e-5)


def test_the_held_prefix_is_an_eighth_of_the_sort(config):
    """`moe.held_row_bound` at 8 of 128: twice the even share of the 131,072 pairs, in whole row tiles."""
    from ray_tpu.models.moe import held_row_bound

    assert held_row_bound(SEQ * 8, 8, 128) == 16384


def test_the_attention_path_is_the_kernels_on_the_chip():
    system = trinity.System.__new__(trinity.System)
    system.cfg = SimpleNamespace(n_head=32, head_dim=128)
    assert system.attention_path(1, SEQ, "tpu") == "pallas" and system.attention_path(1, SEQ, "cpu") == "xla"


def test_the_new_readers_return_nothing_on_a_program_without_the_scopes(named_run):
    """The parent's program: a traced run of it reads no window kind's scope, and its line leaves the entries out
    without raising."""
    readers = Manifest().layer_readers()
    run = dict(named_run, config={"model": "trinity", "batch": {"global_rows": ROWS, "seq": SEQ}},
               summary={**named_run["summary"], "device": {"count": CHIPS}, "span_ms_per_step": {"data_wait": 0.25}},
               peaks=PEAKS)
    assert [readers[name].read(run) for name in NEW] == [None] * len(NEW)
    assert readers["moe.shared_ms"].read(run) is None  # GPT-2 has no scope `shared_expert`
    assert readers["step.dense_mlp_ms"].read(run) is None
    untraced = dict(run, device_trace=None)
    untraced.pop("program_trace", None)
    assert [readers[name].read(untraced) for name in NEW] == [None] * len(NEW)


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


def test_the_window_readers_pick_the_window_kinds_calls_and_the_roofline_divides_the_band_by_them(config, monkeypatch):
    from benchmark.harness import program_trace

    readers = Manifest().layer_readers()
    path = "jit(step_fn)/{phase}(blocks)/layer_scan/while/body/closed_call/attention/{kind}/{walk}/{kernel}/pallas_call"
    window_walk, full_walk = "tiles_90of512/keys_600of720", "tiles_272of512/keys_2112of2176"
    scopes = {
        "fwd_w": path.format(phase="jvp", kind="window", walk=window_walk, kernel="group_8/flash_fwd"),
        "bwd_w": path.format(phase="transpose(jvp", kind="window", walk=window_walk, kernel="flash_bwd"),
        "fwd_d": path.format(phase="jvp", kind="dense_window", walk=window_walk, kernel="group_8/flash_fwd"),
        "fwd_f": path.format(phase="jvp", kind="full", walk=full_walk, kernel="group_8/flash_fwd"),
        "bwd_f": path.format(phase="transpose(jvp", kind="full", walk=full_walk, kernel="flash_bwd"),
        "gmm": "jit(step_fn)/jvp(blocks)/layer_scan/while/body/closed_call/window/moe/experts/gmm_fwd/pallas_call",
    }
    ms = 1_000_000
    ops = [("fwd_w", 0, 4 * ms), ("bwd_w", 5 * ms, 10 * ms), ("fwd_d", 20 * ms, 4 * ms), ("fwd_f", 30 * ms, 15 * ms),
           ("bwd_f", 50 * ms, 33 * ms), ("gmm", 90 * ms, 2 * ms)]
    program = _program(ops, scopes, [(0, 100 * ms)])
    monkeypatch.setattr(program_trace, "of", lambda run: program)
    run = {"config": config, "summary": {"device": {"count": CHIPS}}, "peaks": PEAKS}
    assert readers["kernels.flash_window_ms"].read(run) == pytest.approx(18.0)  # not the full layer's, not the experts'
    assert readers["attn.window_ms"].read(run) == pytest.approx(18.0) and readers["attn.full_ms"].read(run) == pytest.approx(48.0)
    assert readers["swa.walked_over_live_blocks"].read(run) == pytest.approx(720 / 600)
    band_ms = 12 * 128 * 4 * 31_458_304 * 32 / 197e12 * 1e3
    assert readers["kernels.flash_window_roofline"].read(run) == pytest.approx(100 * band_ms / 18.0, rel=1e-9)
    assert readers["kernels.flash_window_roofline"].read({**run, "peaks": None}) is None
    # a program whose flash kernels run under no window kind (Keye's, SDAR's): nothing
    program.scopes = {name: scope.replace("/window/", "/").replace("/dense_window/", "/") for name, scope in scopes.items()}
    assert [readers[name].read(run) for name in NEW if name != "attn.full_ms"] == [None] * 4


def test_the_reference_walks_the_tree_in_the_published_order(config):
    import jax
    import jax.numpy as jnp

    blocks = {"leading": [{"tag": jnp.asarray(0.0)}], "trailing": [],
              "period": [{"tag": jnp.asarray([float(place + 1)])} for place in range(4)]}
    walked = trinity.layers_in_order(blocks, config)
    assert [kind for kind, _ in walked] == ["dense_window", "window", "window", "window", "full"]
    assert [float(jax.tree.leaves(layer)[0]) for _, layer in walked] == [0.0, 1.0, 2.0, 3.0, 4.0]
    assert set(trinity.LEAF_GRAD_REL_TOL) == set(trinity.CHECKED_LEAVES) and len(trinity.CHECKED_LEAVES) == 15
