"""A dress rehearsal of what the next `model_config` PR does to the benchmark:
a model module of another name, a configuration cut to the chip that uses it,
a mix, a fed cell and its per-layer metrics, one of them a Mosaic kernel that
is not a flash kernel, all as new files and appended entries
(`widened_manifest.widen`). The contract tests of `test_benchmark_manifest.py`
and `test_benchmark_program_trace.py` run against this copy too
(`manifest_root`), and the new cell's CPU rehearsal is there. Here: what was
added and that nothing else moved, what the contract refuses, the directory's
tests on a tree that was widened before, and that a kernel of another name
moves none of the four flash metrics."""

import copy
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [REPO, HERE]

from benchmark.harness import peaks  # noqa: E402
from benchmark.harness import program_trace as pt  # noqa: E402
from benchmark.harness import xplane  # noqa: E402
from benchmark.harness.manifest import Manifest, problems, reduced_problems  # noqa: E402
from widened_manifest import (  # noqa: E402,F401  (fixtures)
    OTHER_KERNEL, files_under, named_run, unnamed_run, widen, widened)

FLASH_METRICS = ("kernels.flash_ms", "kernels.flash_fwd_ms", "kernels.flash_bwd_ms",
                 "kernels.flash_roofline")
OTHER_KERNEL_NS = 5000.0


def test_the_widened_copy_is_new_files_and_appended_entries_alone(widened):
    m, base = Manifest(widened.root), Manifest(widened.base)
    assert problems(m) == []
    appended = ("configs", "workloads", "per_layer")
    for key in appended:
        assert m.data[key][:len(base.data[key])] == base.data[key]
    assert {k: v for k, v in m.data.items() if k not in appended} == {
        k: v for k, v in base.data.items() if k not in appended}
    assert [c["name"] for c in m.data["configs"]][len(base.data["configs"]):] == [widened.config]
    assert [w["name"] for w in m.data["workloads"]][len(base.data["workloads"]):] == [widened.cell]
    assert [e["name"] for e in m.data["per_layer"]][len(base.data["per_layer"]):] == widened.metrics
    config = m.config(widened.config)
    assert config["reduced"] == m.data["configs"][-1]["reduced"] == ["n_layer"]
    assert (config["published"]["n_layer"], config["n_layer"]) == (2, 1)
    assert config["model"] != "gpt2"
    now = {os.path.relpath(p, widened.root) for p in files_under(os.path.join(widened.root, "benchmark"))
           if "__pycache__" not in p and "/benchmark/out/" not in p}  # a run's leavings
    assert now - {os.path.relpath(p, widened.root) for p in widened.before} == widened.added
    assert len(widened.added) == 3 + len(widened.metrics)
    assert {p: open(p, "rb").read() for p in widened.before} == widened.before


def test_a_later_fed_cell_brings_the_readings_of_the_entries_that_list_their_cells(widened):
    """`data.wait_ms`, `host.report_ms` and the others that list the fed cells take no
    later cell: the new cell reports each under `<metric>.<configuration>`, a file of
    three lines beside the listed reader, and reads what that reads. The copy lives until
    the next `benchmark` PR puts the cell on the listed entry's own list (PR 50 did, for
    the 53 copies of five configurations)."""
    m = Manifest(widened.root)
    readers = m.layer_readers()
    listing = {e["name"] for e in Manifest(widened.base).data["per_layer"]
               if "gpt2-medium.fed" in e.get("workloads", ())}
    assert set(widened.same_readings.values()) == listing >= {
        "data.wait_ms", "host.h2d_ms", "host.report_ms", "host.report_put_ms", "host.stall_pct"}
    mine = {e["name"] for e in m.metrics_for(widened.cell, "per_layer")}
    assert set(widened.same_readings) <= mine and not listing & mine
    run = {"summary": {"span_ms_per_step": {"data_wait": 1.25, "h2d": 0.5, "report": 0.75},
                       "stall_share": 0.01, "trace_table": None}, "device_trace": None}
    for name, listed in widened.same_readings.items():
        assert readers[name].read(run) == readers[listed].read(run)
        assert {**readers[listed].META, "name": name} == readers[name].META
    assert readers[f"data.wait_ms.{widened.config}"].read(run) == 1.25
    assert readers[f"host.stall_pct.{widened.config}"].read(run) == 1.0


def _one_four_chip_cell_too_many(d):
    while sum(w["chips"] == 4 for w in d["workloads"]) <= max(1, len(d["workloads"]) // 4):
        d["workloads"].append({**d["workloads"][-1], "name": f"four.{len(d['workloads'])}", "chips": 4,
                               "traffic": "fed"})
    return f"{sum(w['chips'] == 4 for w in d['workloads'])} four-chip cells"


def _reduced_disagrees(d):
    d["configs"][-1]["reduced"] = ["n_layer", "n_positions"]
    return "`reduced` is ['n_layer', 'n_positions'] in BENCHMARK.json and ['n_layer'] in its file"


def _cut_without_a_public_source(d):
    d["configs"][-1]["source"] = "none"
    return "its `source` is no URL or differs"


def _a_25th_cell(d):
    d["workloads"] += [{**d["workloads"][-1], "name": f"one.{n}", "traffic": f"mix{n}"}
                       for n in range(len(d["workloads"]), 25)]
    return "25 cells: 1 to 24"


@pytest.mark.parametrize("break_it", [
    _one_four_chip_cell_too_many, _reduced_disagrees, _cut_without_a_public_source, _a_25th_cell,
], ids=lambda break_it: break_it.__name__.strip("_"))
def test_what_the_contract_check_refuses_in_the_widened_copy(widened, break_it):
    m = Manifest(widened.root)
    m.data = copy.deepcopy(m.data)
    complaint = break_it(m.data)
    assert any(complaint in problem for problem in problems(m)), problems(m)


@pytest.mark.parametrize("file_says, complaint", [
    ({"reduced": ["n_layer: 2 -> 1"]}, "not a key's name"),
    ({"published": {}}, "the file holds no `n_layer` beside a different `published.n_layer`"),
    ({"published": {"n_layer": 1}}, "beside a different `published.n_layer`"),
    ({"layout": {"num_workers": 1, "tpus_per_worker": 1, "mesh": None}}, "no `layout.deployment` sentence"),
    ({"source": "https://example.org/another"}, "differs between BENCHMARK.json and its file"),
], ids=["not_a_name", "nothing_published", "published_the_same", "no_deployment", "another_source"])
def test_what_a_cut_configurations_file_has_to_say(widened, file_says, complaint):
    m = Manifest(widened.root)
    entry = dict(m.data["configs"][-1])
    config = {**m.config(entry["name"]), **file_says}
    entry["reduced"] = config["reduced"]
    assert reduced_problems(m.data["configs"][-1], m.config(entry["name"])) == []
    assert any(complaint in problem for problem in reduced_problems(entry, config))


def test_the_directorys_tests_pass_where_the_benchmark_has_been_widened_already(tmp_path):
    """The tree the PR after the next one starts from: `BENCHMARK.json` and
    `benchmark/` already hold a configuration, a cell and metrics more, and
    `tests/benchmark/` is as it is here. There this directory's tests, run as
    they stand, widen that tree once more and pass: none pins what the live
    manifest holds. Left to the run this is part of: this test, and the files
    and the tests that start the benchmark on the CPU (the two cells' own
    rehearsals among them, 61 of this run's 120 s: `test_benchmark_rehearsal.py`
    rehearses a widened copy there), which pin no list of the manifest and
    cost most. What is left takes a minute alone, and the driver's run, six
    files at a time, has seen three times that (PR 50: 102 s alone passed
    `tests/conftest.py`'s 300 s a test)."""
    widen(str(tmp_path))
    shutil.copytree(HERE, tmp_path / "tests" / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    here = "tests/benchmark/test_benchmark_"
    left_out = [f"--ignore={here}{name}.py" for name in ("reference", "aot", "rehearsal")] + [
        f"--deselect={here}widening.py::{test_the_directorys_tests_pass_where_the_benchmark_has_been_widened_already.__name__}",
        f"--deselect={here}manifest.py::test_a_config_a_mix_a_metric_and_a_cell_are_added_as_files_and_appended_entries"] + [
        f"--deselect={here}{cell}.py::test_the_cells_cpu_rehearsal_prints_the_contracts_line" for cell in ("olmoe", "lfm2")]
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH" and not k.startswith("PYTEST_")}
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/benchmark", "-q", "-p", "no:cacheprovider", *left_out],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout[-6000:] + proc.stderr[-3000:]
    summary = proc.stdout.strip().splitlines()[-1]
    assert " passed" in summary and "failed" not in summary and "error" not in summary, summary
    assert int(summary.split(" passed")[0].split()[-1]) >= 60, summary


# --------------------------------------- a Mosaic kernel that is no flash kernel
def _run_of(recorded, config, other_kernel_ns=0.0, attention_by_xla=False):
    """A reader's `run` over a recorded trace; with `other_kernel_ns`, every
    traced step holds one more Mosaic call of that length, named
    `grouped_matmul` by its `pl.pallas_call(name=...)`; with `attention_by_xla`
    the step holds no flash kernel."""
    table = json.load(open(recorded["summary"]["trace_table"]))
    raw = pt.read_xplane(pt.raw_trace_path(recorded))
    dev = table["devices"][0]
    if attention_by_xla:
        dev["ops"] = [op for op in dev["ops"] if op[2] != xplane.MOSAIC_TARGET]
    if other_kernel_ns:
        for _, _, start, _ in xplane.Trace(table).step_runs(dev):
            dev["ops"].append([OTHER_KERNEL + ".7", "custom-call", xplane.MOSAIC_TARGET,
                               "bf16[8,256,64]", start + 1000.0, other_kernel_ns])
        raw["scopes"][OTHER_KERNEL + ".7"] = (
            f"jit(step_fn)/jvp(blocks)/while/body/closed_call/out_mlp/{OTHER_KERNEL}/pallas_call")
    trace = xplane.Trace(table)
    return {"summary": {**recorded["summary"], "device": {"count": 1}}, "device_trace": trace,
            "program_trace": pt.ProgramTrace(trace, raw), "config": config,
            "peaks": peaks.peaks_for("TPU v5 lite")}


def test_a_mosaic_kernel_of_another_name_moves_no_flash_metric(widened, named_run):
    m = Manifest(widened.root)
    readers, config = m.layer_readers(), m.config("gpt2-nano")
    plain, injected = _run_of(named_run, config), _run_of(named_run, config, OTHER_KERNEL_NS)
    without = {name: readers[name].read(plain) for name in FLASH_METRICS}
    assert {name: readers[name].read(injected) for name in FLASH_METRICS} == without
    assert all(value > 0 for value in without.values())
    assert without["kernels.flash_ms"] == plain["device_trace"].mosaic_ms()  # to the last bit
    assert without["kernels.flash_fwd_ms"] + without["kernels.flash_bwd_ms"] == pytest.approx(
        without["kernels.flash_ms"], rel=1e-3)
    # The whole of the Mosaic calls reads more; the kernel's own reader reads the kernel.
    assert injected["device_trace"].mosaic_ms() == pytest.approx(
        without["kernels.flash_ms"] + OTHER_KERNEL_NS / 1e6)
    assert readers[widened.kernel_metric].read(injected) == OTHER_KERNEL_NS / 1e6
    assert readers[widened.kernel_metric].read(plain) is None


def test_where_kernels_carry_names_and_none_is_a_flash_kernel_nothing_is_read(widened, named_run):
    """Attention on the XLA path beside a named expert matmul: the step's only
    Mosaic call is not attention, and no flash metric reports it as such."""
    m = Manifest(widened.root)
    readers = m.layer_readers()
    run = _run_of(named_run, m.config("gpt2-nano"), OTHER_KERNEL_NS, attention_by_xla=True)
    assert run["device_trace"].mosaic_ms() == OTHER_KERNEL_NS / 1e6
    assert {name: readers[name].read(run) for name in FLASH_METRICS} == dict.fromkeys(FLASH_METRICS)
    assert readers[widened.kernel_metric].read(run) == OTHER_KERNEL_NS / 1e6


def test_where_no_mosaic_call_carries_a_name_all_of_them_count(unnamed_run):
    """PR 22's trace, or any tree before PR 24: `closed_call/pallas_call` and no name."""
    readers = Manifest().layer_readers()
    trace = unnamed_run["device_trace"]
    assert readers["kernels.flash_ms"].read(unnamed_run) == trace.mosaic_ms() == pytest.approx(30604e-6)
    assert readers["kernels.flash_roofline"].read({
        **unnamed_run, "config": Manifest().config("gpt2-nano"), "peaks": peaks.peaks_for("TPU v5 lite"),
        "summary": {**unnamed_run["summary"], "device": {"count": 1}}}) > 0


def test_without_the_raw_trace_no_flash_metric_is_read(named_run, tmp_path):
    """The table alone cannot tell one Mosaic call from another: the four say
    nothing (and a traced line that lacks them is refused), not a sum that may
    hold another kernel."""
    table = tmp_path / "cell.7.trace.json"  # as `WorkerRun.finish` leaves it, with no `trace/` beside it
    shutil.copy(named_run["summary"]["trace_table"], table)
    readers = Manifest().layer_readers()
    run = {"summary": {"trace_table": str(table), "device": {"count": 1}},
           "device_trace": named_run["device_trace"], "config": Manifest().config("gpt2-nano"),
           "peaks": peaks.peaks_for("TPU v5 lite")}
    assert run["device_trace"].mosaic_ms() > 0 and pt.of(run) is None
    assert {name: readers[name].read(run) for name in FLASH_METRICS} == dict.fromkeys(FLASH_METRICS)
    assert readers["kernels.flash_ms"].read({"summary": {"trace_table": None}, "device_trace": None}) is None
