"""BENCHMARK.json against the contract's checks that need no chip, and the
proof that the harness is driven by data. The tests that take `manifest_root`
state the contract and not the contents: each runs on the live manifest and
on a copy widened by new files and appended entries (`widened_manifest.py`), so what
a later PR appends passes them without an edit here."""

import glob
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [REPO, os.path.dirname(os.path.abspath(__file__))]

from benchmark.harness import driver, peaks, rehearsal_names  # noqa: E402
from benchmark.harness.manifest import Manifest, problems, reduced_problems  # noqa: E402
from widened_manifest import hold_the_room, manifest_root, rehearsals_own, room, widened  # noqa: E402,F401  (fixtures)

SEED_CELLS = {"gpt2-medium.resident": 1, "gpt2-medium.fed": 1, "gpt2-xl-fsdp4.fed": 4}  # PR 22's
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


def run_benchmark(root, *args, env=None, timeout=300):
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
        cwd=root, env={**os.environ, **(env or {})}, capture_output=True, text=True,
        timeout=timeout)
    lines = proc.stdout.strip().splitlines()
    return proc, lines[-1] if lines else ""


def test_manifest_meets_the_contract(manifest_root):
    assert problems(Manifest(manifest_root)) == []


def test_every_entry_has_one_reader_file_and_every_reader_file_one_entry(manifest_root):
    """`layer_readers()` finds a reader by its `META["name"]`, so a second file of one name would
    stand in the first one's place unseen, and a file no entry names is read by no run."""
    m = Manifest(manifest_root)
    files = glob.glob(os.path.join(m.dir, "layer_metrics", "*.py"))
    readers = m.layer_readers()
    entries = [e["name"] for e in m.data["per_layer"]]
    assert len(files) == len(readers) == len(entries), (len(files), len(readers), len(entries))
    assert set(readers) == set(entries), set(readers) ^ set(entries)


def test_per_layer_of_the_live_list_is_held_to_the_contracts_cap():
    """The contract caps the `per_layer` of the live `BENCHMARK.json` at 128, and that is what the list may hold:
    `widened_manifest.room`, the one count, which leaves a rehearsal's own entries out and so reads the same here and
    where `test_benchmark_widening.py` runs this directory's tests inside a copy widened before. Nothing is set aside
    for the rehearsals' copies, which no driver reads and nothing holds to the cap. Only that bound is held, and here
    alone: a `model_config` PR may edit nothing in this directory, so a margin asserted on the live manifest would
    refuse the very PR the room is for. With 93 entries held (PR 69) a `model_config` PR may append **35**; a fed
    expert cell with a window brings eighteen copies of listed readings beside its own."""
    hold_the_room(Manifest().data["per_layer"])


def _held(live, rehearsed):
    """A `per_layer` of `live` entries, and with `rehearsed` the seven more a copy widened before holds."""
    entries = [{"name": f"reading.{n}", "workloads": ["some-cell.fed"]} for n in range(live)]
    return entries + [{"name": f"throwaway.{n}", "workloads": ["throwaway-cut.short"]} for n in range(7 * rehearsed)]


@pytest.mark.parametrize("rehearsed", [False, True], ids=["live", "widened_before"])
@pytest.mark.parametrize("live, left", [(93, 35), (127, 1), (128, 0), (129, -1)])
def test_an_appended_entry_passes_at_127_held_and_fails_at_129_in_either_run(live, left, rehearsed):
    per_layer = _held(live, rehearsed)
    assert room(per_layer) == left
    if left >= 0:
        hold_the_room(per_layer)
    else:
        with pytest.raises(AssertionError, match="holds 129 .* may hold 128, the contract's cap.* 1 over"):
            hold_the_room(per_layer)


def test_the_bound_is_one_number_on_the_live_manifest_and_on_a_copy_widened_before(widened):
    """What `test_benchmark_widening.py`'s nested run stands on: there `Manifest()` is a copy widened once, and its
    count of the room is this manifest's, the rehearsal's seven entries left out."""
    here, there = Manifest().data["per_layer"], Manifest(widened.root).data["per_layer"]
    appended = len(widened.metrics)
    assert len(there) == len(here) + appended and len(rehearsals_own(there)) == len(rehearsals_own(here)) + appended
    assert [e["name"] for e in rehearsals_own(there)][-appended:] == widened.metrics
    assert room(there) == room(here)


def test_the_names_before_the_fold_go_on_a_rehearsals_line_and_on_no_other():
    """Three CPU rehearsal tests outside this directory still look a listed reading up under
    `<metric>.<configuration>` (`harness/rehearsal_names.py`, to delete with them): `driver.main` adds
    those names to a rehearsal's line, and the function that writes the chip's line has no part in it."""
    m = Manifest()
    cell, reading = m.cell("glm-4.7-flash-ep8-l5.fed4k"), {"value": 1.25, "unit": "ms/step"}
    line = {"metrics": {"rehearsal.data.wait_ms": reading, "rehearsal.step.device_ms": reading}}
    rehearsal_names.add_names_before_the_fold(line, m, cell)
    assert line["metrics"] == {"rehearsal.data.wait_ms": reading, "rehearsal.step.device_ms": reading,
                               "rehearsal.data.wait_ms.glm-4.7-flash-ep8-l5": reading}
    chips = {"metrics": {"data.wait_ms": reading, "step.device_ms": reading}}
    rehearsal_names.add_names_before_the_fold(chips, m, cell)
    assert set(chips["metrics"]) == {"data.wait_ms", "step.device_ms"}
    assert "rehearsal_names" not in driver.result_line.__code__.co_names
    assert "rehearsal_names" in driver.main.__code__.co_names


def test_the_seeds_cells_are_there_and_four_chip_cells_keep_to_their_share(manifest_root):
    cells = {w["name"]: w["chips"] for w in Manifest(manifest_root).data["workloads"]}
    assert SEED_CELLS.items() <= cells.items()
    assert sum(chips == 4 for chips in cells.values()) <= max(1, len(cells) // 4)
    assert len(cells) <= 24


def test_every_cell_resolves_to_files_and_reports_both_levels(manifest_root):
    m = Manifest(manifest_root)
    readers = m.layer_readers()
    entries = {c["name"]: c for c in m.data["configs"]}
    for w in m.data["workloads"]:
        config, mix, entry = m.config(w["config"]), m.traffic(w["traffic"]), entries[w["config"]]
        # A cut is written down the same in both places: each key, what the source has and
        # what is run here, and for what deployment. The rule is `problems()`'s own.
        assert reduced_problems(entry, config) == []
        assert config["layout"]["num_workers"] * config["layout"]["tpus_per_worker"] == w["chips"]
        assert os.path.isfile(os.path.join(m.dir, "loops", mix["loop"] + ".py"))
        assert os.path.isfile(os.path.join(m.dir, "models", config["model"] + ".py"))
        reports = {e["name"] for e in m.metrics_for(w["name"], "end_to_end")}
        assert "setup_s" in reports and len(reports) >= 2
        if w["name"] in SEED_CELLS:
            assert reports == {"tokens_per_s_per_chip", "setup_s"}
        for e in m.metrics_for(w["name"], "per_layer"):
            assert callable(readers[e["name"]].read)
    for name in ("gpt2-medium", "gpt2-xl-fsdp4"):  # full depth and width, by name
        assert m.config(name)["reduced"] == entries[name]["reduced"] == []
    collective = {e["name"] for e in m.metrics_for("gpt2-xl-fsdp4.fed", "per_layer")} - {
        e["name"] for e in m.metrics_for("gpt2-medium.fed", "per_layer")}
    assert collective >= {"collectives.total_ms", "collectives.exposed_ms"}


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


def test_a_config_a_mix_a_metric_and_a_cell_are_added_as_files_and_appended_entries(widened):
    """A later PR's whole change to the benchmark: new files, and entries
    appended to BENCHMARK.json (`widened_manifest.widen`: a model module, a cut
    configuration, a mix, a cell, its metrics). No file that is there is
    edited, and the new cell runs, from the copy's own `benchmark/`, and
    reports the new metrics."""
    assert problems(Manifest(widened.root)) == []
    # A checkout holds the program beside the benchmark and the workers import both from
    # its root: so here, through the copy's link to `ray_tpu`, and no `PYTHONPATH`.
    proc, last = run_benchmark(
        widened.root, "--workload", widened.cell, "--seed", "2147483659", "--seconds", "2",
        "--trace", "1", "--rehearse-cpu", env={"PYTHONPATH": ""})
    # The worker built its system from the copy's model module, which the repo has not.
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(last)
    assert set(line) == CONTRACT_KEYS | {"breakdown"} and line["correct"] is True
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    steps, kernel = widened.metrics[:2]
    assert line["metrics"][f"rehearsal.{steps}"]["value"] == line["attempted"] > 0
    # No Mosaic kernel runs on the CPU: the reader finds nothing and the line leaves it out.
    assert f"rehearsal.{kernel}" not in line["metrics"]
    # What the entries that list the fed cells read there, this fed cell reads under its own names.
    for name in ("data.wait_ms", "host.h2d_ms", "host.report_ms"):
        assert line["metrics"][f"rehearsal.{name}.{widened.config}"]["value"] > 0
        assert f"rehearsal.{name}" not in line["metrics"]
    # The metric of the four-chip cell alone is not this cell's.
    assert "rehearsal.collectives.total_ms" not in line["metrics"]
    assert {p: open(p, "rb").read() for p in widened.before} == widened.before
