"""BENCHMARK.json against the contract's checks that need no chip, and the
proof that the harness is driven by data."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import peaks  # noqa: E402
from benchmark.harness.manifest import Manifest, problems  # noqa: E402

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_benchmark(root, *args, env=None, timeout=300):
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
        cwd=root, env={**os.environ, **(env or {})}, capture_output=True, text=True,
        timeout=timeout)
    lines = proc.stdout.strip().splitlines()
    return proc, lines[-1] if lines else ""


def test_manifest_meets_the_contract():
    assert problems(Manifest()) == []


def test_cells_are_the_three_of_the_issue_and_one_takes_four_chips():
    cells = {w["name"]: w["chips"] for w in Manifest().data["workloads"]}
    assert cells == {"gpt2-medium.resident": 1, "gpt2-medium.fed": 1, "gpt2-xl-fsdp4.fed": 4}


def test_every_cell_resolves_to_files_and_reports_both_levels():
    m = Manifest()
    readers = m.layer_readers()
    for w in m.data["workloads"]:
        config, mix = m.config(w["config"]), m.traffic(w["traffic"])
        assert config["reduced"] == [] and config["layout"]["num_workers"] * config["layout"][
            "tpus_per_worker"] == w["chips"]
        assert os.path.isfile(os.path.join(m.dir, "loops", mix["loop"] + ".py"))
        assert os.path.isfile(os.path.join(m.dir, "models", config["model"] + ".py"))
        assert {e["name"] for e in m.metrics_for(w["name"], "end_to_end")} == {
            "tokens_per_s_per_chip", "setup_s"}
        for e in m.metrics_for(w["name"], "per_layer"):
            assert callable(readers[e["name"]].read)
    collective = {e["name"] for e in m.metrics_for("gpt2-xl-fsdp4.fed", "per_layer")} - {
        e["name"] for e in m.metrics_for("gpt2-medium.fed", "per_layer")}
    assert collective == {"collectives.total_ms", "collectives.exposed_ms"}


def test_configs_keep_the_published_widths():
    m = Manifest()
    medium, xl = m.config("gpt2-medium"), m.config("gpt2-xl-fsdp4")
    assert (medium["n_layer"], medium["n_head"], medium["n_embd"]) == (24, 16, 1024)
    assert (xl["n_layer"], xl["n_head"], xl["n_embd"]) == (48, 25, 1600)
    for c in (medium, xl):
        assert (c["n_positions"], c["vocab_size"], c["padded_vocab_size"]) == (1024, 50257, 50304)


def test_peaks_are_the_v5e_and_an_unknown_device_is_an_error():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert (v5e["bf16_flops_per_s"], v5e["hbm_bytes_per_s"], v5e["ici_bits_per_s"]) == (
        197e12, 819e9, 1600e9)
    with pytest.raises(KeyError, match="no peaks on record"):
        peaks.peaks_for("TPU v9 imaginary")


def test_a_run_off_the_tpu_fails_and_prints_no_result():
    proc, last = run_benchmark(REPO, "--workload", "gpt2-medium.resident", "--seconds", "1",
                               env={"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "BENCHMARK FAILED" in proc.stdout and not last.startswith("{")


def test_alone_with_its_manifest_the_benchmark_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "gpt2-medium.resident"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    lines = proc.stdout.strip().splitlines()
    assert proc.returncode != 0 and not (lines and lines[-1].startswith("{"))


def test_a_config_a_mix_a_metric_and_a_cell_are_added_as_files_and_appended_entries(tmp_path):
    """A later PR's whole change to the benchmark: three new files and
    entries appended to BENCHMARK.json. No file that is there is edited, and
    the new cell runs and reports the new metric."""
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    before = {p: open(p, "rb").read() for p in _files(tmp_path / "benchmark")}
    bench = tmp_path / "benchmark"
    config = json.load(open(bench / "configs" / "gpt2-nano.json"))
    config.update(name="throwaway-nano", n_head=4, batch={"global_rows": 4, "seq": 32})
    json.dump(config, open(bench / "configs" / "throwaway-nano.json", "w"))
    mix = json.load(open(bench / "traffic" / "fed.json"))
    mix.update(name="throwaway-short-docs", block_rows=16)
    mix["documents"].update(median_tokens=20, max_tokens=200)
    json.dump(mix, open(bench / "traffic" / "throwaway-short-docs.json", "w"))
    (bench / "layer_metrics" / "throwaway_steps.py").write_text(
        'META = {"name": "throwaway.steps", "unit": "steps", "better": "higher",\n'
        '        "source": "program_counter", "layer": "step", "moves": "tokens_per_s_per_chip"}\n\n\n'
        'def read(run):\n    return run["summary"]["completed"]\n')
    manifest = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    manifest["configs"].append({
        "name": "throwaway-nano", "source": "none", "file": "benchmark/configs/throwaway-nano.json",
        "reduced": [], "why": "test"})
    manifest["workloads"].append({
        "name": "throwaway-nano.short", "config": "throwaway-nano",
        "traffic": "throwaway-short-docs", "chips": 1, "why": "test"})
    manifest["per_layer"].append({
        "name": "throwaway.steps", "unit": "steps", "better": "higher", "source": "program_counter",
        "layer": "step", "moves": "tokens_per_s_per_chip", "workloads": ["throwaway-nano.short"]})
    json.dump(manifest, open(tmp_path / "BENCHMARK.json", "w"))

    assert problems(Manifest(str(tmp_path))) == []
    proc, last = run_benchmark(
        str(tmp_path), "--workload", "throwaway-nano.short", "--seed", "5", "--seconds", "2",
        "--trace", "1", "--rehearse-cpu", env={"PYTHONPATH": REPO})
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(last)
    assert set(line) == CONTRACT_KEYS | {"breakdown"} and line["correct"] is True
    assert line["device"]["platform"] == "cpu"
    assert line["metrics"]["rehearsal.throwaway.steps"]["value"] == line["attempted"] > 0
    # The metric of the four-chip cell alone is not this cell's.
    assert "rehearsal.collectives.total_ms" not in line["metrics"]
    assert {p: open(p, "rb").read() for p in before} == before


def _files(root):
    return [os.path.join(d, f) for d, _, fs in os.walk(root) for f in fs]
