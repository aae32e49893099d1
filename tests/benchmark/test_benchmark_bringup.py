"""`benchmark/harness/bringup.py` and the ten readers on it (ISSUE 37): on the
record a traced CPU rehearsal leaves, on a run whose process kept no report
(the recorded traces), and their entries against the contract."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [REPO, os.path.dirname(os.path.abspath(__file__))]

from benchmark.harness import bringup, driver  # noqa: E402
from benchmark.harness.manifest import Manifest, problems  # noqa: E402
from widened_manifest import named_run  # noqa: E402,F401  (fixture)

CELL, SEED = "gpt2-medium.fed", 37
EVERYWHERE = ["entry.spawn_s", "entry.backend_start_s", "entry.device_touch_s",
              "entry.session_start_s", "entry.unaccounted_s", "compile.trace_s",
              "compile.lower_s", "compile.cache_read_s", "compile.traces"]
GANG_ONLY = "entry.gang_join_s"


@pytest.fixture(scope="module")
def rehearsed():
    """A traced CPU rehearsal of the one-chip fed cell: its output and the
    record it left in `benchmark/out/`."""
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", str(SEED),
         "--seconds", "2", "--trace", "1", "--rehearse-cpu"],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    with open(os.path.join(REPO, "benchmark", "out", f"{CELL}.{SEED}.json")) as fh:
        record = json.load(fh)
    return proc.stdout, record


def test_the_entries_are_appended_and_meet_the_contract():
    m = Manifest()
    assert problems(m) == []
    names = [e["name"] for e in m.data["per_layer"]]
    # Appended by PR 37 as one run, later PRs append after it; 36 since PR 50 folded the 18 copies that stood before it.
    first = names.index(EVERYWHERE[0])
    assert first >= 36 and names[first:first + 10] == EVERYWHERE[:3] + [GANG_ONLY] + EVERYWHERE[3:]
    for e in m.data["per_layer"][first:first + 10]:
        assert e["moves"] == "setup_s" and e["better"] == "lower"
        assert e["source"] == ("program_span" if e["name"].startswith("entry.") else "program_counter")
        # The gang's seam lists four-chip cells, the first of them GPT-2's; the others list none: every cell.
        if e["name"] == GANG_ONLY:
            assert e["workloads"][0] == "gpt2-xl-fsdp4.fed" and all(m.cell(c)["chips"] == 4 for c in e["workloads"])
        else:
            assert "workloads" not in e
    for cell in m.data["workloads"]:
        listed = {e["name"] for e in m.metrics_for(cell["name"], "per_layer")}
        assert set(EVERYWHERE) <= listed
        # Every four-chip cell reads the gang's join and no other cell does: on the entry's own list (PR 56
        # folded Olmo-Hybrid's copy into it), or as the `<metric>.<config>` copy a later cell brings.
        assert bool({GANG_ONLY, f"{GANG_ONLY}.{cell['config']}"} & listed) == (cell["chips"] == 4)


def test_the_rehearsals_line_reads_all_nine_and_the_seams_add_up(rehearsed):
    stdout, record = rehearsed
    metrics = record["line"]["metrics"]
    for name in EVERYWHERE:
        assert metrics["rehearsal." + name]["value"] is not None, name
    assert "rehearsal." + GANG_ONLY not in metrics  # one worker is no gang
    value = {n: metrics["rehearsal." + n]["value"] for n in EVERYWHERE}
    loop_entered = record["summary"]["t_loop_wall"] - record["parent"]["t_fit_wall"]
    assert (value["entry.spawn_s"] + value["entry.backend_start_s"] + value["entry.session_start_s"]
            + value["entry.unaccounted_s"]) == pytest.approx(loop_entered, abs=0.05)
    assert 0 <= value["entry.unaccounted_s"] < 0.25 * loop_entered
    assert 0 < value["entry.device_touch_s"] <= value["entry.backend_start_s"]
    assert value["compile.traces"] > 0 and value["compile.trace_s"] > 0 and value["compile.lower_s"] > 0
    assert value["compile.cache_read_s"] == 0.0  # a rehearsal keeps out of the persistent cache
    # Two instruments, one quantity: the program's counter and the benchmark's listener.
    counter = record["summary"]["compile_counter"]["totals"]
    heard = record["summary"]["compiles_setup"]["seconds"] + record["summary"]["compiles_in_window"]["seconds"]
    assert counter["backend_s"] == pytest.approx(heard, rel=0.01)
    assert counter["compiles"] == record["summary"]["compiles_setup"]["count"]
    assert len(record["summary"]["compile_counter"]["top_functions"]) == bringup.TOP_FUNCTIONS
    assert "worker.first_report" in record["summary"]["bringup"]["timeline_s"]
    assert "[run] bring-up s by span" in stdout and "[run] compile counter, rank 0" in stdout


def test_nothing_is_read_where_the_process_kept_no_report(named_run):  # noqa: F811
    readers = Manifest().layer_readers()
    for name in EVERYWHERE + [GANG_ONLY]:
        assert readers[name].read(named_run) is None
    assert named_run["bringup"] is None
    # A run of this process's own, made before any fit(): no report is its.
    run = {"parent": {"t_fit_wall": 4e9}, "summary": {"t_loop_wall": 4e9 + 1}}
    assert bringup.of(run) is None


def _span(name, start, end, parent="r", **attributes):
    return {"name": name, "kind": "bringup", "trace_id": "t", "span_id": name + str(attributes),
            "parent_id": parent, "start": start, "end": end, "status": "OK", "pid": 1,
            "attributes": {"gang": "g", **attributes}}


def _report_made_by_hand():
    """A gang of two: rank 1 imports a second longer, rank 0 opens its chip half a second longer."""
    w, b = bringup.WORKER, bringup.BRINGUP
    return {"compile": {"rank0": {"traces": 7, "trace_s": 1.5, "lower_s": 0.5, "cache_read_s": 0.25,
                                  "functions": {"f": {"traces": 7, "trace_s": 1.5, "lowerings": 1,
                                                      "lower_s": 0.5, "compiles": 1, "backend_s": 2.0}}}},
            "bringup": [
        _span(bringup.ROOT, 100.0, 160.0, None),
        _span(b + "placement", 100.5, 101.0), _span(b + "spawn", 101.0, 103.0),
        _span(b + "backend", 103.5, 113.5),
        _span(w + "import_jax", 104.0, 106.0, rank=0), _span(w + "import_jax", 104.0, 107.0, rank=1),
        _span(w + "distributed_init", 106.0, 110.0, rank=0), _span(w + "distributed_init", 107.0, 110.0, rank=1),
        _span(w + "device_touch", 110.0, 113.0, rank=0), _span(w + "device_touch", 110.0, 112.5, rank=1),
        _span(b + "session", 114.0, 114.5),
        _span(w + "first_report", 115.0, 130.0, rank=0), _span(w + "first_report", 116.0, 130.0, rank=1)]}


def _record_of_a_run(rehearse=False):
    """What `driver.run` returns, as far as `driver.set_up` reads it: the process started at 95 s, called
    `fit()` at 100 s, the loop was entered at 115.5 s and the first timed step began at 140 s."""
    return {"rehearse": rehearse, "parent": {"t_start_wall": 95.0, "t_fit_wall": 100.0},
            "summary": {"t_loop_wall": 115.5, "window_wall_start": 140.0}}


def test_the_readers_on_a_report_made_by_hand():
    report = _report_made_by_hand()
    run = {"parent": {"t_fit_wall": 100.0}, "summary": {"t_loop_wall": 115.5}}
    got = bringup.Bringup(report, run)
    assert got.spawn_s == 2.5 and got.backend_start_s == 10.0
    assert got.device_touch_s == 5.5  # rank 1: 3.0 + 2.5; rank 0: 2.0 + 3.0
    assert got.chip_open_s == 3.0  # the open alone, by the slowest rank: what `setup_s` leaves out
    assert got.gang_join_s == 4.0 and got.session_start_s == 1.0
    # 15.5 s less placement 0.5, spawn 2.0, backend 10.0, session -> entered 1.0
    assert got.unaccounted_s == pytest.approx(2.0)
    assert got.spawn_s + got.backend_start_s + got.session_start_s + got.unaccounted_s == pytest.approx(15.5)
    assert got.timeline()["worker.import_jax"] == [2.0, 3.0] and got.timeline()["spawn"] == [2.0, 2.0]
    assert got.top_functions() == [["f", 2.0, 7, 1, 2.0]]


# ------------------------------------------------- `setup_s`: the interval less the chip's open (ISSUE 56)
def test_setup_s_is_the_interval_less_the_slowest_ranks_open(monkeypatch, capsys):
    monkeypatch.setattr(bringup, "kept_report", lambda run: _report_made_by_hand())
    run = _record_of_a_run()
    # 140 - 95 = 45 s from process start to the first timed step; rank 0 was 3.0 s in `device_touch`, rank 1 2.5 s.
    assert driver.set_up(run) == {"whole_s": 45.0, "chip_open_s": 3.0, "setup_s": 42.0}
    assert run["bringup"].chip_open_s == 3.0  # read once: the readers of a traced run find it there
    assert "[run] bring-up s by span" in capsys.readouterr().out  # traced or not


def test_setup_s_leaves_the_import_in(monkeypatch):
    """`import_jax` runs this repository's `configure_compile_cache` and an import a change can make longer: it
    stays in `setup_s`, and `entry.device_touch_s`, which adds it to the open, is the per-layer reading it was."""
    report = _report_made_by_hand()
    for span in report["bringup"]:
        if span["name"].endswith("import_jax"):
            span["end"] += 20.0
    monkeypatch.setattr(bringup, "kept_report", lambda run: report)
    run = _record_of_a_run()
    assert driver.set_up(run)["chip_open_s"] == 3.0 and run["bringup"].device_touch_s == 25.5


@pytest.mark.parametrize("kept", [False, True], ids=["no_report_kept", "a_report_without_the_span"])
def test_a_chip_run_with_no_open_span_has_no_setup_s(monkeypatch, kept):
    report = _report_made_by_hand() if kept else None
    if kept:
        report["bringup"] = [s for s in report["bringup"] if s["name"] != bringup.CHIP_OPEN_SPAN]
    monkeypatch.setattr(bringup, "kept_report", lambda run: report)
    with pytest.raises(driver.Failed, match="ray_tpu.train.worker.device_touch"):
        driver.set_up(_record_of_a_run())
    # A rehearsal takes out what there is: a lone CPU worker opens no span.
    assert driver.set_up(_record_of_a_run(rehearse=True)) == {"whole_s": 45.0, "chip_open_s": 0.0, "setup_s": 45.0}


def test_the_failure_is_the_commands_and_prints_no_result(monkeypatch, capsys):
    """`driver.main` on a chip record whose report lacks the span: `BENCHMARK FAILED` naming it, exit 1, no line."""
    record = {**_record_of_a_run(), "cell": {"name": "gpt2-medium.resident"}, "seed": 1}
    monkeypatch.setattr(driver, "run", lambda argv, t_start, manifest: record)
    monkeypatch.setattr(bringup, "kept_report", lambda run: None)
    assert driver.main([], 95.0) == 1
    out = capsys.readouterr().out.strip().splitlines()
    assert out[-1].startswith("BENCHMARK FAILED: Failed: ") and bringup.CHIP_OPEN_SPAN in out[-1]
    assert not [line for line in out if line.startswith("{")]
