"""What the granite-4.0-h-micro configuration brings to the benchmark: its file against the catalog row, its cell and
entries appended and held to the contract, its readers on a made trace, the scan's floors and the parameter count by
hand. A one-chip cell. One of the listed readings, `step.dense_mlp_ms`, comes as a `<metric>.<configuration>` copy until
a `benchmark` PR folds it into the listed entry's own list. `per_layer` holds 121 of the contract's 128 with this cell and
no more: `widened_manifest.widen()` rehearses a further cell of seven entries on top, and the accepted cells' own tests
hold the widened copy to 128 too (ISSUE 73 asked for four host-clock copies besides: left out for that, CHANGES.md).
(The cell's CPU rehearsal was walked by hand, `benchmark/run.py --rehearse-cpu`; the eight readings are a device
trace's, there is none to read off the chip, and they are held here on a made one.)"""

import json
import math
import os
import sys
from types import SimpleNamespace

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import listed_readings  # noqa: E402
from benchmark.harness.manifest import Manifest, problems, reduced_problems  # noqa: E402
from benchmark.models import granite_hybrid  # noqa: E402
from widened_manifest import named_run  # noqa: E402,F401  (fixture)

CONFIG = "granite-4.0-h-micro-l10"
CELL = CONFIG + ".fed4k"
ROWS, SEQ, CHIPS = 1, 4096, 1
NEW = ("ssd.mixer_ms", "ssd.conv_ms", "ssd.gates_ms", "kernels.ssd_fwd_ms", "kernels.ssd_bwd_ms", "kernels.ssd_ms",
       "kernels.ssd_roofline")
COPIED = ("step.dense_mlp_ms",)
REDUCED = ["num_hidden_layers", "layer_types", "vocab_size"]
V5E_HBM_BYTES = 16_909_336_064
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def config():
    return Manifest().config(CONFIG)


def test_the_manifest_holds_the_cell_appended_and_meets_the_contract():
    m = Manifest()
    assert problems(m) == []
    cells = [w["name"] for w in m.data["workloads"]]
    assert cells.index(CELL) >= 13 and m.cell(CELL) == {**m.cell(CELL), "config": CONFIG, "traffic": "fed4k", "chips": 1}
    entry = next(e for e in m.data["configs"] if e["name"] == CONFIG)
    assert m.data["configs"].index(entry) >= 12
    assert entry["reduced"] == REDUCED and reduced_problems(entry, m.config(CONFIG)) == []
    assert os.path.isfile(os.path.join(m.dir, "models", m.config(CONFIG)["model"] + ".py"))
    assert all(1 <= len(e["why"]) <= 200 for e in m.data["configs"] + m.data["workloads"])
    for said in ("1 x 4,096", "nine of ten mixers the SSD scan", "64 heads on one B / C", "SwiGLU 8,192", "3 %", "6 %",
                 "AdamW walks 772 M", "2 rows do not fit"):
        assert said in m.cell(CELL)["why"], said
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024
    # One run of eight behind the 113 entries PR 70 left: the seven new readings, then the copy.
    names = [e["name"] for e in m.data["per_layer"]]
    first = names.index(NEW[0])
    assert first >= 113 and names[first:first + 7] == list(NEW)
    assert names[first + 7:first + 8] == [f"{name}.{CONFIG}" for name in COPIED] and len(names) <= 128
    assert sum(w["chips"] == 4 for w in m.data["workloads"]) >= 2 and len(cells) >= 14
    # The mix is the one that was there, unedited: rows of 4,097 out of 16-row blocks.
    assert m.traffic("fed4k") == {**m.traffic("fed4k"), "loop": "fed", "block_rows": 16, "supply_factor": 4}
    assert m.traffic("fed4k")["documents"] == {"median_tokens": 400, "sigma": 1.2, "min_tokens": 8, "max_tokens": 8192}


def test_the_cell_reports_the_new_readings_the_copies_and_every_unlisted_one():
    by_name, unlisted = listed_readings.holds_for(CELL, [], list(NEW) + [f"{name}.{CONFIG}" for name in COPIED])
    readers = Manifest().layer_readers()
    assert len(unlisted) >= 30 and {"step.device_ms", "step.mfu_pct", "kernels.flash_ms", "kernels.flash_roofline",
                                    "device.idle_pct", "step.product_floor_ms", "step.xla_remat_ms", "compile.traces"} <= unlisted
    for name in NEW:
        assert by_name[name]["moves"] == "tokens_per_s_per_chip" and by_name[name]["source"] == "device_trace"
        assert readers[name].META == {k: v for k, v in by_name[name].items() if k != "workloads"}
        assert by_name[name]["layer"] == ("kernels" if name.startswith("kernels.") else "linear attention")
    assert (by_name[NEW[6]]["unit"], by_name[NEW[6]]["better"]) == ("%", "higher")
    for name in COPIED:  # a copy is the listed entry under the cell's name, read by the listed reader
        copy, listed = by_name[f"{name}.{CONFIG}"], by_name[name]
        assert copy == {**listed, "name": copy["name"], "workloads": [CELL]} and CELL not in listed["workloads"]
        assert listed["workloads"] == listed_readings.TABLE.get(name, listed["workloads"])
        assert readers[copy["name"]].read.__code__.co_filename == readers[name].__file__  # `read = listed.read`
        assert readers[copy["name"]].META == {k: v for k, v in copy.items() if k != "workloads"}
    m = Manifest()
    assert [e["name"] for e in m.metrics_for(CELL, "end_to_end")] == ["tokens_per_s_per_chip", "setup_s"]
    assert 121 <= len(m.data["per_layer"]) <= 128  # 121 here, 128 with the rehearsal's seven: the fold comes first


def test_the_file_holds_every_published_key_and_cuts_depth_and_vocabulary_and_no_width(config):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        published = next(json.loads(line) for line in fh if json.loads(line)["name"] == "granite-4.0-h-micro")
    differ = {k for k, v in published["config"].items() if config.get(k, "missing") != v}
    assert differ == set(REDUCED) and config["reduced"] == REDUCED
    assert config["source"] == published["source_url"]
    assert config["published"] == {k: published["config"][k] for k in REDUCED}
    assert [config[k] for k in REDUCED] == [10, ["mamba"] * 5 + ["attention"] + ["mamba"] * 4, 12544]
    assert config["published"]["layer_types"][:10] == config["layer_types"]  # published layers 0-9: one whole period
    assert config["published"]["layer_types"] == config["layer_types"] * 4
    assert config["vocab_size"] * 8 == 100352  # an eighth of the vocabulary, the guide's floor
    widths = {"hidden_size": 2048, "shared_intermediate_size": 8192, "num_attention_heads": 32, "num_key_value_heads": 8,
              "mamba_n_heads": 64, "mamba_d_head": 64, "mamba_d_state": 128, "mamba_n_groups": 1, "mamba_d_conv": 4,
              "mamba_expand": 2, "embedding_multiplier": 12, "attention_multiplier": 0.015625, "residual_multiplier": 0.22,
              "logits_scaling": 8, "rms_norm_eps": 1e-5, "mamba_chunk_size": 256}
    for width, value in widths.items():
        assert config[width] == published["config"][width] == value, width
    assert config["layout"] == {**config["layout"], "num_workers": 1, "tpus_per_worker": 1, "mesh": None}
    for said in ("one chip", "four such chips as pipeline stages", "published layers 0-9", "eight ways", "rows 0-12,543",
                 "layers, heads and widths whole", "not run", "nothing stands in", "reference alike"):
        assert said in config["layout"]["deployment"], said
    assert config["batch"] == {**config["batch"], "global_rows": ROWS, "seq": SEQ}
    for said in ("772 M", "12.35 GB", "do not fit"):
        assert said in config["batch"]["why"], said
    assert config["remat_policy"] == "save_attn" and config["rehearse_with"] == "granite-hybrid-nano"
    assert config["ssd_chunk"] == 128 and config["predicted_tokens_per_s_per_chip"] == 16000
    for said in ("block", "mlp", "mamba_mixer", "attention_mixer", "ends", "document_boundaries", "initialisation",
                 "ssd_chunk", "optimizer", "precision", "remat_policy", "loss_band"):
        assert len(config["assumed"][said]) > 40, said
    assert "decays every leaf" in config["assumed"]["optimizer"] and "A_log" in config["assumed"]["optimizer"]
    memory = config["memory_analysis_v5e_bytes"]
    assert memory["arguments"] < memory["peak"] < V5E_HBM_BYTES and memory["peak"] > 0.25 * V5E_HBM_BYTES
    lo, hi = config["loss_band"]
    assert lo < math.log(12544) + 0.02 ** 2 * 2048 / 64 / 2 < hi  # ln 12,544 and half the logits' variance after the 1/8
    assert "check_tolerances" not in config and "check_tolerances" in Manifest().config("granite-hybrid-nano")


def test_the_parameter_count_by_hand(config):
    d, ff, inner, channels, heads = 2048, 8192, 4096, 4352, 64
    mamba = granite_hybrid.layer_params(config, "mamba")
    assert mamba == {"mlp": 3 * d * ff, "norms": 2 * d, "w_in": d * (inner + channels + heads), "w_out": inner * d,
                     "conv": 5 * channels, "heads": 3 * heads, "gate_norm": inner}
    assert (mamba["w_in"], mamba["w_out"], mamba["conv"], mamba["mlp"]) == (17_432_576, 8_388_608, 21_760, 50_331_648)
    attention = granite_hybrid.layer_params(config, "attention")
    assert attention == {"mlp": 3 * d * ff, "norms": 2 * d, "attention": 2 * 4_194_304 + 2 * 1_048_576}
    assert (sum(mamba.values()), sum(attention.values())) == (76_182_976, 60_821_504)  # ISSUE 73's two layers
    total = 9 * 76_182_976 + 60_821_504 + 12544 * d + d
    assert granite_hybrid.num_params(config) == total == 772_160_448  # 12.35 GB at 16 B a parameter
    assert 16 * total == pytest.approx(12.35e9, rel=1e-3)
    assert 12544 * d / total == pytest.approx(0.033, abs=1e-3)  # the head 3 % of the parameters held; the model's 6 %
    assert 100352 * d / (4 * (total - 12544 * d - d) + 100352 * d + d) == pytest.approx(0.064, abs=1e-3)


def test_the_arithmetic_by_hand(config):
    d = 2048
    matmul = 9 * (17_432_576 + 8_388_608 + 50_331_648) + (10_485_760 + 50_331_648) + 12544 * d
    assert granite_hybrid.matmul_params(config) == matmul == 771_883_008
    assert 10 * 50_331_648 / matmul == pytest.approx(0.652, abs=1e-3)  # the SwiGLU of 8,192: ~2/3 of the FLOPs
    # the scan at chunk 128, a token and head: C B^T once a group of 64 (2 x 128 x 128 / 64 = 512), the masked product
    # and the two state products (2 x 128 x 64 and 2 x 128 x 64 each: 16,384)
    assert granite_hybrid.ssd_flops_per_token(config, backward=False) == 512 + 16384 + 2 * 16384
    assert granite_hybrid.ssd_flops_per_token(config) == (512 + 3 * 16384) + (512 + 2 * 16384 + 4 * 16384 + 2 * 512)
    assert granite_hybrid.ssd_flops_per_step(config, ROWS, SEQ) == 149_504 * SEQ * 64 * 9 == pytest.approx(352.7e9, rel=1e-3)
    # bytes a token: v, o, do, dv of 64 x 64 bf16 and B, C, dB, dC of 128 bf16; three f32 rows of 64 gates; the states
    # of 64 x 128 x 64 f32 a chunk of 128, written and read
    a_token = 2 * (4 * 4096 + 4 * 128) + 4 * 3 * 64 + 2 * 64 * 128 * 64 * 4 / 128
    assert granite_hybrid.ssd_bytes_per_step(config, ROWS, SEQ) == a_token * SEQ * 9 == pytest.approx(2.482e9, rel=1e-3)
    # HBM binds: 3.03 ms of bytes against 1.79 ms of products
    assert granite_hybrid.ssd_bytes_per_step(config, ROWS, SEQ) / 819e9 == pytest.approx(3.030e-3, rel=1e-3)
    assert granite_hybrid.ssd_flops_per_step(config, ROWS, SEQ) / 197e12 == pytest.approx(1.790e-3, rel=1e-3)
    per_token = granite_hybrid.train_flops_per_token(config, SEQ)
    assert per_token == pytest.approx(6.0 * matmul + 6.0 * d * (SEQ + 1) + 149_504 * 64 * 9, rel=1e-12)
    assert per_token * SEQ == pytest.approx(19.53e12, rel=1e-3)  # ISSUE 73's 19.0 TFLOP of products, attention and the scan
    # the flash kernels: one call over the triangle, 32 query heads on 8 key/value heads of 64
    triangle = SEQ * (SEQ + 1) // 2
    assert granite_hybrid.flash_flops_per_step(config, ROWS, SEQ) == 12 * 64 * triangle * 32
    act, stat = SEQ * 64 * 2, SEQ * 4
    assert granite_hybrid.flash_bytes_per_step(config, ROWS, SEQ) == 32 * (6 * act + 3 * stat) + 8 * 6 * act


def test_the_programs_own_count_agrees(config):
    from ray_tpu.models import granite_hybrid as program
    from ray_tpu.ops import ssd

    cfg = granite_hybrid.granite_hybrid_config(config)
    assert program.num_params(cfg) == granite_hybrid.num_params(config)
    assert (cfg.n_head, cfg.n_kv_head, cfg.head_dim, cfg.d_model, cfg.d_ff) == (32, 8, 64, 2048, 8192)
    assert (cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state, cfg.mamba_groups, cfg.conv_channels) == (64, 64, 128, 1, 4352)
    assert cfg.period == cfg.layer_types == tuple(config["layer_types"]) and cfg.norm_eps == 1e-5
    assert (cfg.embedding_multiplier, cfg.attention_multiplier, cfg.residual_multiplier, cfg.logits_scaling) == (12.0, 1 / 64, 0.22, 8.0)
    assert cfg.ssd_chunk == config["ssd_chunk"] == ssd.CHUNK  # the chunk the kernels' scope declares
    program_flops = program.train_flops_per_token(cfg, SEQ)  # the scan's products left out
    assert granite_hybrid.train_flops_per_token(config, SEQ) - program_flops == pytest.approx(149_504 * 64 * 9)


def test_the_attention_path_is_the_kernels_on_the_chip():
    system = granite_hybrid.System.__new__(granite_hybrid.System)
    system.cfg = SimpleNamespace(n_head=32, head_dim=64)
    assert system.attention_path(1, SEQ, "tpu") == "pallas" and system.attention_path(1, SEQ, "cpu") == "xla"


def test_the_new_readers_return_nothing_on_a_program_without_what_they_read(named_run):
    """The parent's program: a traced run of it carries no `ssd` scope and no `ssd_*` kernel, and its line leaves the
    entries out without raising."""
    readers = Manifest().layer_readers()
    run = dict(named_run, config={"model": "granite_hybrid", "batch": {"global_rows": ROWS, "seq": SEQ}},
               summary={**named_run["summary"], "device": {"count": CHIPS}, "span_ms_per_step": {"data_wait": 0.25}},
               peaks=PEAKS)
    assert [readers[name].read(run) for name in NEW] == [None] * len(NEW)
    untraced = dict(run, device_trace=None)
    untraced.pop("program_trace", None)
    assert [readers[name].read(untraced) for name in NEW] == [None] * len(NEW)


def _program(ops, scopes, steps):
    """A `ProgramTrace` of one device: `ops` [name, start, dur], Mosaic calls but for names ending `fusion`."""
    from benchmark.harness import xplane

    dev = {"ops": [[name, "fusion", "", 0, start, dur] if name.endswith("fusion")
                   else [name, "custom-call", xplane.MOSAIC_TARGET, 0, start, dur] for name, start, dur in ops]}
    trace = SimpleNamespace(devices=[dev], _leaf_ops=lambda d: d["ops"],
                            step_runs=lambda d: [(0, 0, start, dur) for start, dur in steps])
    trace.per_step = lambda d, pick: [
        xplane.measure(xplane.clip(((op[4], op[4] + op[5]) for op in d["ops"] if pick(op)), start, start + dur))
        for start, dur in steps]
    return SimpleNamespace(trace=trace, scopes=scopes, kernel=None)


def test_the_readers_read_the_kernels_by_name_the_scopes_and_the_floor(config, monkeypatch):
    from benchmark.harness import program_trace

    readers = Manifest().layer_readers()
    walk = "attention/ssd/ssd_scan/chunk_128/heads_4of64/group_64"
    scopes = {
        "fwd": f"jit(step_fn)/jvp(blocks)/layer_scan/while/body/closed_call/{walk}/ssd_fwd/pallas_call",
        "bwd": f"jit(step_fn)/transpose(jvp(blocks))/layer_scan/while/body/closed_call/{walk}/ssd_bwd/pallas_call",
        "conv_bwd": "jit(step_fn)/transpose(jvp(blocks))/layer_scan/while/body/closed_call/qkv/qkv/checkpoint/ssd/ssd_conv/"
                    "tile_128/rows_4096/short_conv_bwd/pallas_call",
        "gates.fusion": "jit(step_fn)/jvp(blocks)/layer_scan/while/body/closed_call/attention/ssd/ssd_gates/mul",
        "sum.fusion": "jit(step_fn)/jvp(blocks)/layer_scan/while/body/closed_call/attention/ssd/ssd_scan/cumsum",
        "flash": "jit(step_fn)/jvp(blocks)/layer_scan/while/body/closed_call/attention/tiles_20of32/flash_fwd/pallas_call",
    }
    ms = 1_000_000
    ops = [("fwd", 0, 7 * ms), ("bwd", 10 * ms, 12 * ms), ("conv_bwd", 25 * ms, 3 * ms), ("gates.fusion", 30 * ms, 2 * ms),
           ("sum.fusion", 33 * ms, 1 * ms), ("flash", 40 * ms, 5 * ms)]
    program = _program(ops, scopes, [(0, 50 * ms)])
    program.kernel = lambda name: program_trace.kernel_ms(program.trace, program.scopes, name)
    monkeypatch.setattr(program_trace, "of", lambda run: program)
    run = {"config": config, "summary": {"device": {"count": CHIPS}}, "peaks": PEAKS}
    read = lambda name: readers[name].read(run)  # noqa: E731
    assert (read("kernels.ssd_fwd_ms"), read("kernels.ssd_bwd_ms"), read("kernels.ssd_ms")) == (
        pytest.approx(7.0), pytest.approx(12.0), pytest.approx(19.0))
    assert read("ssd.mixer_ms") == pytest.approx(25.0) and read("ssd.conv_ms") == pytest.approx(3.0)
    assert read("ssd.gates_ms") == pytest.approx(2.0)
    floor_ms = max(352.7e9 / 197e12, 2.482e9 / 819e9) * 1e3  # the bytes': 3.03 ms
    assert read("kernels.ssd_roofline") == pytest.approx(100 * floor_ms / 19.0, rel=1e-3) and read("kernels.ssd_roofline") < 100
    # a later program that runs the scan without these kernels: the time is what stands under `ssd_scan`
    program.scopes = {name: scope.replace("ssd_fwd", "other_fwd").replace("ssd_bwd", "other_bwd") for name, scope in scopes.items()}
    assert read("kernels.ssd_fwd_ms") is None and read("kernels.ssd_ms") == pytest.approx(20.0)  # with the running sum
    assert read("kernels.ssd_roofline") == pytest.approx(100 * floor_ms / 20.0, rel=1e-3)


def test_the_reference_is_written_from_the_description_and_walks_the_tree_in_the_published_order(config):
    import jax
    import jax.numpy as jnp

    blocks = {"leading": [], "trailing": [], "period": [{"tag": jnp.asarray([float(place)])} for place in range(10)]}
    walked = granite_hybrid.layers_in_order(blocks, config)
    assert [kind for kind, _ in walked] == config["layer_types"]
    assert [float(jax.tree.leaves(layer)[0]) for _, layer in walked] == [float(place) for place in range(10)]
    assert set(granite_hybrid.LEAF_GRAD_REL_TOL) == set(granite_hybrid.CHECKED_LEAVES) and len(granite_hybrid.CHECKED_LEAVES) == 7
    source = open(granite_hybrid.__file__).read()
    reference = source[source.index("# ------------------------------------------------------------------- reference"):
                       source.index("# Tolerances of the agreement")]
    assert "ray_tpu" not in reference and "def ssd_recurrence" in reference and "jax.lax.scan(position" in reference
    assert 'jax.default_matmul_precision("highest")' in reference
