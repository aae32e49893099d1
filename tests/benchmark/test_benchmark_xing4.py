"""What the Xing4.0-29B-A4B configuration brings to the benchmark: its file against the catalog row, its cell and entries
appended and held to the contract, its readers on a recorded trace and on a made one, the kernels' floors and the
parameter count by hand. A one-chip cell. It brought sixteen of the listed readings as `<metric>.<configuration>` copies
(PR 66); PR 69 put it on those entries' own lists (`listed_readings.TABLE`), deleted the copies, and gave the mixes' two
backward kernels of PR 67 their readings.
(The cell's CPU rehearsal is `tests/test_xing4_rehearsal.py`: this directory's tests are run a second time inside
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
from benchmark.models import xing4  # noqa: E402
from widened_manifest import named_run  # noqa: E402,F401  (fixture)

CONFIG = "xing4-29b-a4b-ep8-l5"
CELL = CONFIG + ".fed4k"
ROWS, SEQ, CHIPS = 1, 4096, 1
STREAMS = ("mhc.mix_ms", "mhc.maps_ms", "mhc.sinkhorn_ms", "mhc.mix_roofline", "mhc.res_sum_err")
KERNELS = ("kernels.mhc_bwd_ms", "kernels.mhc_bwd_roofline")  # PR 69: `mhc_post_bwd` + `mhc_pre_bwd`, and their bytes' floor
NEW = STREAMS + KERNELS
# The listed readings a one-chip fed cell with a leading dense layer, held experts, a shared one and latent attention reports in
# every traced run: it brought them as copies and joined the lists at PR 69 (three traced runs of the cell on the chip read
# each, `benchmark/testdata/xing4_traced_lines.json`). Not `data.fetch_block_ms` and not `host.stall_pct`: a 16-row block
# lasts 16 steps of a row, and a window holds about 93 steps, under three readings at each of the clock's 8 positions.
LISTED = ("data.wait_ms", "host.h2d_ms", "host.report_ms", "host.report_put_ms", "moe.router_ms", "moe.dispatch_ms",
          "moe.experts_ms", "moe.experts_roofline", "moe.load_max_over_mean", "kernels.gmm_ms", "kernels.gmm_roofline",
          "moe.held_pairs_share", "moe.issued_over_held", "moe.shared_ms", "step.dense_mlp_ms", "mla.latent_ms")
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size", "num_nextn_predict_layers"]
V5E_HBM_BYTES = 16_909_336_064
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def config():
    return Manifest().config(CONFIG)


def test_the_manifest_holds_the_cell_appended_and_meets_the_contract():
    m = Manifest()
    assert problems(m) == []
    cells = [w["name"] for w in m.data["workloads"]]
    assert cells[11] == CELL and m.cell(CELL) == {**m.cell(CELL), "config": CONFIG, "traffic": "fed4k", "chips": 1}
    entry = m.data["configs"][10]
    assert entry["name"] == CONFIG and entry["reduced"] == REDUCED and reduced_problems(entry, m.config(CONFIG)) == []
    assert os.path.isfile(os.path.join(m.dir, "models", m.config(CONFIG)["model"] + ".py"))
    assert all(1 <= len(e["why"]) <= 200 for e in m.data["configs"] + m.data["workloads"])
    for said in ("1 x 4,096", "four streams", "20 Sinkhorn rounds", "keys 192 / values 128", "8 of 64 experts", "759 M"):
        assert said in m.cell(CELL)["why"], said
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024
    # One run of five after the 86 entries PR 65 left, the streams' readings (the sixteen copies that followed went in
    # PR 69), then the two kernels' readings that PR 69 appended.
    names = [e["name"] for e in m.data["per_layer"]]
    assert names[86:91] == list(STREAMS) and names[91:93] == list(KERNELS)
    assert not [name for name in names if name.endswith("." + CONFIG)]
    assert sum(w["chips"] == 4 for w in m.data["workloads"]) == 2 and len(cells) >= 12  # a third four-chip slot is open
    # The mix is the one that was there, unedited: rows of 4,097 out of 16-row blocks.
    assert m.traffic("fed4k") == {**m.traffic("fed4k"), "loop": "fed", "block_rows": 16, "supply_factor": 4}
    assert m.traffic("fed4k")["documents"] == {"median_tokens": 400, "sigma": 1.2, "min_tokens": 8, "max_tokens": 8192}


def test_the_cell_reports_the_new_readings_each_listed_one_and_every_unlisted_one():
    m = Manifest()
    by_name, unlisted = listed_readings.holds_for(CELL, LISTED, NEW)
    readers = m.layer_readers()
    assert len(unlisted) >= 30 and {"step.mfu_pct", "kernels.flash_ms", "kernels.flash_roofline", "step.product_floor_ms",
                                    "step.xla_remat_ms", "step.unowned_ms", "compile.traces"} <= unlisted
    for name in NEW:
        assert by_name[name]["moves"] == "tokens_per_s_per_chip"
        assert by_name[name]["layer"] == ("residual streams" if name in STREAMS else "kernels")
        assert readers[name].META == {k: v for k, v in by_name[name].items() if k != "workloads"}
    assert (by_name["mhc.mix_roofline"]["unit"], by_name["mhc.mix_roofline"]["better"]) == ("%", "higher")
    assert by_name["mhc.res_sum_err"]["source"] == "program_counter"
    assert [(by_name[n]["unit"], by_name[n]["better"], by_name[n]["source"]) for n in KERNELS] == [
        ("ms/step", "lower", "device_trace"), ("%", "higher", "device_trace")]
    assert [e["name"] for e in m.metrics_for(CELL, "end_to_end")] == ["tokens_per_s_per_chip", "setup_s"]
    # the 52 it had, sixteen now under the listed names, and the two kernels'; an unlisted reading appended later joins them
    assert len(m.metrics_for(CELL, "per_layer")) == len(LISTED) + len(NEW) + len(unlisted) >= 54


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(REPO, "benchmark", "testdata", "xing4_traced_lines.json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("name", LISTED + KERNELS)
def test_a_reading_the_cell_joined_reads_a_number_in_every_recorded_traced_run(name, recorded):
    """An entry lists a cell only where its reader returns a value in every traced run of that cell (a listed reading
    that comes back `null` refuses the next `benchmark` PR): the lines of three traced runs of this cell on the chip,
    and what the span and counter readers read there, through the readers as they stand."""
    m = Manifest()
    reader, mine = m.layer_readers()[name], [e["name"] for e in m.metrics_for(CELL, "per_layer") if "workloads" in e]
    assert recorded["cell"] == CELL and name in mine and len({run["seed"] for run in recorded["runs"]}) >= 3
    for run in recorded["runs"]:
        line = run["metrics"]
        assert run["correct"] is True and run["failed"] == 0 and run["device"]["platform"] == "tpu"
        assert isinstance(line[name], float) and 0 < line[name] < float("inf")
        assert set(mine) <= set(line) and not [n for n in line if n.endswith("." + CONFIG)]
        if name in ("data.wait_ms", "host.h2d_ms", "host.report_ms", "moe.load_max_over_mean", "moe.held_pairs_share",
                    "moe.issued_over_held"):  # the others read the raw trace
            assert reader.read({"summary": run["summary"], "device_trace": None}) == line[name]
        if name in KERNELS:  # the two kernels are part of the scope's time, and no share of a floor passes 100 %
            assert line["kernels.mhc_bwd_ms"] < line["mhc.mix_ms"] and line["kernels.mhc_bwd_roofline"] < 100


def test_the_file_holds_every_published_key_and_cuts_counts_and_no_width(config):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        published = next(json.loads(line) for line in fh if json.loads(line)["name"] == "Xing4.0-29B-A4B")
    differ = {k for k, v in published["config"].items() if config.get(k, "missing") != v}
    assert differ == set(REDUCED) and config["reduced"] == REDUCED
    assert config["source"] == published["source_url"]
    assert config["published"] == {k: published["config"][k] for k in REDUCED}
    assert [config[k] for k in REDUCED] == [5, 1, 8, 16384, 0]
    assert (config["first_expert_held"], xing4.router_width(config), config["num_experts_per_tok"]) == (0, 64, 4)
    assert config["vocab_size"] * 8 == 131072  # an eighth of the vocabulary, the guide's floor
    for width in ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads", "num_key_value_heads",
                  "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok",
                  "n_shared_experts", "hc_mult", "hc_sinkhorn_iters", "hc_eps", "rope_scaling", "routed_scaling_factor"):
        assert config[width] == published["config"][width], width
    assert (config["hidden_size"], config["num_attention_heads"], xing4.head_dim(config), config["v_head_dim"],
            config["hc_mult"], config["hc_sinkhorn_iters"]) == (3584, 32, 192, 128, 4, 20)
    assert config["layout"] == {**config["layout"], "num_workers": 1, "tpus_per_worker": 1, "mesh": None}
    for said in ("eight-chip", "8 of its 64", "16,384 of the 131,072", "every head", "pipeline stages", "prediction module on the last",
                 "not run", "reference alike"):
        assert said in config["layout"]["deployment"], said
    assert config["batch"] == {**config["batch"], "global_rows": ROWS, "seq": SEQ}
    for said in ("256 each", "an eighth", "Two rows do not fit"):
        assert said in config["batch"]["why"], said
    assert config["remat_policy"] == "save_attn" and config["rehearse_with"] == "xing4-nano"
    for said in ("streams", "maps", "sinkhorn", "maps_init", "streams_dtype", "rope", "yarn", "norms", "expert_bias", "aux_loss",
                 "n_routed_experts", "num_nextn_predict_layers", "init", "optimizer", "learning_rate", "remat_policy"):
        assert len(config["assumed"][said]) > 40, said
    memory = config["memory_analysis_v5e_bytes"]
    assert memory["arguments"] < memory["peak_memory"] <= 15.5e9 and memory["peak_memory"] > 0.25 * V5E_HBM_BYTES
    lo, hi = config["loss_band"]
    assert lo < 9.704 + 1.197 ** 2 / 2 < hi  # ln(16,384) and half the logits' variance at the seeded weights
    assert "check_tolerances" not in config and "check_tolerances" in Manifest().config("xing4-nano")


def test_the_parameter_count_by_hand(config):
    d = 3584
    attention = d * 768 + 768 * 32 * 192 + d * 576 + 512 * 32 * 256 + 32 * 128 * d
    assert attention == 28_409_856 == xing4.attention_matmul_params(config)
    norms = 2 * d + 768 + 512
    streams = 2 * (4 * d * 24 + 3 + 24)  # a sublayer's Phi, three scales, 24 biases: twice a layer
    assert streams == 688_182
    dense = 3 * d * 9216
    expert = 3 * d * 1024
    moe = d * 64 + 64 + expert + 8 * expert  # the router whole, its bias, the shared expert whole, 8 of 64 experts
    assert (dense, expert, moe) == (99_090_432, 11_010_048, 99_319_872)
    total = (attention + norms + streams + dense) + 4 * (attention + norms + streams + moe) + 2 * 16384 * d + d
    assert xing4.num_params(config) == total == 759_346_446  # 759.3 M: 12.15 GB at 16 B a parameter
    assert xing4.sublayers(config) == 10 and xing4.phi_entries(config) == 14336 * 24


def test_the_arithmetic_by_hand(config):
    d, f = 3584, 1024
    attention = 28_409_856
    active = (5 * attention + 3 * d * 9216 + 4 * (d * 64 + 3 * d * f * (1 + 4 * 8 / 64)) + 16384 * d + 10 * 14336 * 24)
    assert xing4.active_matmul_params(config) == pytest.approx(active, rel=1e-12)
    per_token = xing4.train_flops_per_token(config, SEQ)
    assert per_token == pytest.approx(6.0 * active + 6.0 * 5 * 32 * (192 + 128) * SEQ, rel=1e-12)
    assert per_token * SEQ == pytest.approx(14.25e12, rel=2e-3)  # ISSUE 66's 14.2 TFLOP a step
    assert 6.0 * 10 * 14336 * 24 / per_token == pytest.approx(0.0059, abs=2e-4)  # the maps' products: 0.6 % of the FLOPs
    assert xing4.held_pairs_per_layer(config, SEQ) == 2048  # 256 a held expert
    assert xing4.moe_expert_flops_per_step(config, ROWS, SEQ) == pytest.approx(6 * 3 * d * f * 2048 * 4)
    assert xing4.moe_expert_bytes_per_step(config, ROWS, SEQ) == pytest.approx(2 * 9 * (2048 * d + 8 * d * f + 2048 * f) * 4)
    # the flash kernels by the published widths: q . k, dq, dk at 192; p . v, dv, dp at 128; the causal half
    assert xing4.flash_flops_per_step(config, ROWS, SEQ) == 3 * (192 + 128) * SEQ * SEQ * 32 * 5
    key, value, stat = SEQ * 192 * 2, SEQ * 128 * 2, SEQ * 4
    assert xing4.flash_bytes_per_step(config, ROWS, SEQ) == 32 * 5 * (6 * key + 5 * value + 3 * stat)
    assert xing4.flash_flops_per_step(config, ROWS, SEQ) / 197e12 == pytest.approx(13.08e-3, rel=1e-3)  # the products bind
    assert xing4.flash_bytes_per_step(config, ROWS, SEQ) / 819e9 < 3e-3
    # padded to 192 the same kernels would be asked 3 x 384: the floor counts what the model needs
    assert xing4.flash_flops_per_step(config, ROWS, SEQ) * 384 / 320 == 3 * 384 * SEQ * SEQ * 32 * 5
    # the stream mixes: a sublayer's forward reads 4 + 1 + 4 and writes 1 + 4 planes of 4,096 x 3,584 bf16; backward twice that
    plane = SEQ * d * 2
    assert xing4.mhc_mix_bytes_per_step(config, ROWS, SEQ) == 3 * 14 * plane * 10 == 12_331_253_760
    assert xing4.mhc_mix_bytes_per_step(config, ROWS, SEQ) / 819e9 == pytest.approx(15.06e-3, rel=1e-3)
    # the mixes' two backward kernels, a sublayer: `mhc_post_bwd` reads g, X (4 planes each) and y and writes dX (4) and dy,
    # 14 planes, beside a token's 4 + 16 map values in and their gradients out (f32); `mhc_pre_bwd` reads X and writes
    # dX, 8 planes, beside a token's 24 columns of r dm and one coefficient in (f32), Phi (24 x 14,336 f32) in, dPhi out
    post = 14 * plane + 2 * SEQ * 20 * 4
    pre = 8 * plane + SEQ * 25 * 4 + 2 * 24 * 14336 * 4
    assert (post, pre) == (411_697_152, 238_043_136)
    assert xing4.mhc_bwd_bytes_per_step(config, ROWS, SEQ) == 10 * (post + pre) == 6_497_402_880
    assert xing4.mhc_bwd_bytes_per_step(config, ROWS, SEQ) / 819e9 == pytest.approx(7.933e-3, rel=1e-3)
    # of the 1.23 GB a sublayer's whole backward moves (PR 67) the kernels' part is 0.65; the forward is the scope's alone
    assert (post + pre) / 1.23e9 == pytest.approx(0.528, abs=2e-3)
    assert xing4.mhc_bwd_bytes_per_step(config, ROWS, SEQ) < xing4.mhc_mix_bytes_per_step(config, ROWS, SEQ)
    assert xing4.mhc_bwd_bytes_per_step(config, 2, SEQ) == 10 * (2 * post + 2 * pre - 2 * 24 * 14336 * 4)  # Phi once a call


def test_the_programs_own_count_agrees(config):
    from ray_tpu.models import xing4 as program

    cfg = xing4.xing4_config(config)
    assert program.num_params(cfg) == xing4.num_params(config)
    assert program.train_flops_per_token(cfg, SEQ) == pytest.approx(xing4.train_flops_per_token(config, SEQ), rel=1e-12)
    assert (cfg.n_head, cfg.head_dim, cfg.v_head_dim, cfg.d_ff, cfg.d_expert) == (32, 192, 128, 9216, 1024)
    assert (cfg.n_experts, cfg.held, cfg.first_expert_held, cfg.experts_per_token, cfg.routed_scaling_factor) == (64, 8, 0, 4, 2.0)
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps, cfg.hc_clamp, cfg.norm_eps) == (4, 20, 1e-6, (-30.0, 30.0), 1e-6)
    assert cfg.yarn.correction_range(64, cfg.rope_theta) == (10, 23) and cfg.softmax_scale == pytest.approx(0.14468, rel=1e-4)
    assert program.layer_kinds(cfg) == ("latent_dense",) + ("latent_moe",) * 4


def test_the_attention_path_is_the_kernels_on_the_chip():
    system = xing4.System.__new__(xing4.System)
    system.cfg = SimpleNamespace(n_head=32, head_dim=192)
    assert system.attention_path(1, SEQ, "tpu") == "pallas" and system.attention_path(1, SEQ, "cpu") == "xla"


def test_the_new_readers_return_nothing_on_a_program_without_the_scopes(named_run):
    """The parent's program: a traced run of it reads no `mhc` scope, and its line leaves the entries out without raising."""
    readers = Manifest().layer_readers()
    run = dict(named_run, config={"model": "xing4", "batch": {"global_rows": ROWS, "seq": SEQ}},
               summary={**named_run["summary"], "device": {"count": CHIPS}, "check": {}}, peaks=PEAKS)
    assert [readers[name].read(run) for name in NEW] == [None] * len(NEW)
    assert readers["moe.shared_ms"].read(run) is None  # GPT-2 has no scope `shared_expert`
    assert readers["mla.latent_ms"].read(run) is None
    untraced = dict(run, device_trace=None)
    untraced.pop("program_trace", None)
    assert [readers[name].read(untraced) for name in NEW] == [None] * len(NEW)


def test_the_stream_readers_pick_their_scopes_and_the_roofline_divides_the_bytes_by_the_whole(config, monkeypatch):
    from benchmark.harness import program_trace, xplane

    readers = Manifest().layer_readers()
    body = "jit(step_fn)/{phase}(blocks)/layer_scan/while/body/closed_call/"
    scopes = {
        "maps_f": body.format(phase="jvp") + "qkv/checkpoint/mhc/maps/bnsd,cnd->cbs/dot_general",
        "sink_b": body.format(phase="transpose(jvp") + "checkpoint/mhc/sinkhorn/div",
        "pre_r": body.format(phase="transpose(jvp") + "checkpoint/rematted_computation/mhc/pre/mul",
        "post_b": body.format(phase="transpose(jvp") + "checkpoint/mhc/post/add",
        "out": "jit(step_fn)/transpose(jvp(mhc))/out/reduce_sum",  # opened outside `blocks`: wrapped, and still `mhc`
        "maps_of_another": "jit(step_fn)/jvp(blocks)/maps/mul",  # `maps` without `mhc`: not this layer's
        "latent": body.format(phase="jvp") + "qkv/checkpoint/mla_latent/dot_general",
    }
    ms = 1_000_000
    ops = [("maps_f", 0, 3 * ms), ("sink_b", 4 * ms, 1 * ms), ("pre_r", 6 * ms, 2 * ms), ("post_b", 9 * ms, 5 * ms),
           ("out", 15 * ms, 1 * ms), ("maps_of_another", 20 * ms, 7 * ms), ("latent", 30 * ms, 4 * ms)]
    dev = {"ops": [[name, "fusion", "", 0, start, dur] for name, start, dur in ops]}
    trace = SimpleNamespace(devices=[dev], _leaf_ops=lambda d: d["ops"], step_runs=lambda d: [(0, 0, 0, 100 * ms)])
    monkeypatch.setattr(program_trace, "of", lambda run: SimpleNamespace(trace=trace, scopes=scopes))
    run = {"config": config, "summary": {"device": {"count": CHIPS}, "check": {"res_sum_err": 0.0178}}, "peaks": PEAKS}
    assert readers["mhc.mix_ms"].read(run) == pytest.approx(12.0)  # maps + sinkhorn + pre + post + out; not `maps` bare
    assert readers["mhc.maps_ms"].read(run) == pytest.approx(3.0) and readers["mhc.sinkhorn_ms"].read(run) == pytest.approx(1.0)
    assert readers["mhc.mix_roofline"].read(run) == pytest.approx(100 * 12_331_253_760 / 819e9 * 1e3 / 12.0, rel=1e-9)
    assert readers["mhc.mix_roofline"].read({**run, "peaks": None}) is None
    assert readers["mhc.res_sum_err"].read(run) == 0.0178
    assert xplane.measure(xplane.union([(0, 3), (2, 5)])) == 5  # the union the readers take


def test_the_kernel_readers_add_the_two_kernels_up_and_divide_their_bytes_floor_by_that(config, monkeypatch):
    from benchmark.harness import program_trace

    readers = Manifest().layer_readers()
    times = {"mhc_post_bwd": 5.89, "mhc_pre_bwd": 3.79, "flash_bwd": 19.9}  # PR 67's trace: ten calls of 0.59 and of 0.38
    monkeypatch.setattr(program_trace, "of", lambda run: SimpleNamespace(kernel=run["kernels"].get))
    run = {"config": config, "summary": {"device": {"count": CHIPS}}, "peaks": PEAKS, "kernels": times}
    assert readers["kernels.mhc_bwd_ms"].read(run) == pytest.approx(9.68)
    assert readers["kernels.mhc_bwd_roofline"].read(run) == pytest.approx(100 * 6_497_402_880 / 819e9 * 1e3 / 9.68, rel=1e-9)
    assert readers["kernels.mhc_bwd_roofline"].read(run) == pytest.approx(81.96, abs=0.01)
    assert readers["kernels.mhc_bwd_roofline"].read({**run, "peaks": None}) is None
    one_alone = {**run, "kernels": {"mhc_post_bwd": 5.89}}  # a later PR that takes a kernel off the path: silent, not smaller
    assert [readers[name].read(one_alone) for name in KERNELS] == [None, None]
    monkeypatch.setattr(program_trace, "of", lambda run: None)  # an untraced run
    assert [readers[name].read(run) for name in KERNELS] == [None, None]


def test_the_reference_walks_the_tree_in_the_published_order(config):
    import jax
    import jax.numpy as jnp

    params = {"blocks": {"leading": [{"tag": jnp.asarray(0.0)}], "trailing": [],
                         "period": [{"tag": jnp.arange(1.0, 5.0)}]}}
    walked = xing4.layers_in_order(params, config)
    assert [float(jax.tree.leaves(layer)[0]) for layer in walked] == [0.0, 1.0, 2.0, 3.0, 4.0]
    assert set(xing4.LEAF_GRAD_REL_TOL) == set(xing4.CHECKED_LEAVES) and len(xing4.CHECKED_LEAVES) == 10
    assert xing4.RES_SUM_ERR_TOL == 0.1 and xing4.FLIPPED_SHARE_TOL == 1.6e-2
