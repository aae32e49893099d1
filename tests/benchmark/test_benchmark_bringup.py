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

from benchmark.harness import bringup  # noqa: E402
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
        assert e.get("workloads") == (["gpt2-xl-fsdp4.fed"] if e["name"] == GANG_ONLY else None)
    for cell in m.data["workloads"]:
        listed = {e["name"] for e in m.metrics_for(cell["name"], "per_layer")}
        assert set(EVERYWHERE) <= listed
        assert (GANG_ONLY in listed) == (cell["chips"] == 4)


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


def test_the_readers_on_a_report_made_by_hand():
    def span(name, start, end, parent="r", **attributes):
        return {"name": name, "kind": "bringup", "trace_id": "t", "span_id": name + str(attributes),
                "parent_id": parent, "start": start, "end": end, "status": "OK", "pid": 1,
                "attributes": {"gang": "g", **attributes}}

    w, b = bringup.WORKER, bringup.BRINGUP
    report = {"compile": {"rank0": {"traces": 7, "trace_s": 1.5, "lower_s": 0.5, "cache_read_s": 0.25,
                                    "functions": {"f": {"traces": 7, "trace_s": 1.5, "lowerings": 1,
                                                        "lower_s": 0.5, "compiles": 1, "backend_s": 2.0}}}},
              "bringup": [
        span(bringup.ROOT, 100.0, 160.0, None),
        span(b + "placement", 100.5, 101.0), span(b + "spawn", 101.0, 103.0),
        span(b + "backend", 103.5, 113.5),
        span(w + "import_jax", 104.0, 106.0, rank=0), span(w + "import_jax", 104.0, 107.0, rank=1),
        span(w + "distributed_init", 106.0, 110.0, rank=0), span(w + "distributed_init", 107.0, 110.0, rank=1),
        span(w + "device_touch", 110.0, 113.0, rank=0), span(w + "device_touch", 110.0, 112.5, rank=1),
        span(b + "session", 114.0, 114.5),
        span(w + "first_report", 115.0, 130.0, rank=0), span(w + "first_report", 116.0, 130.0, rank=1)]}
    run = {"parent": {"t_fit_wall": 100.0}, "summary": {"t_loop_wall": 115.5}}
    got = bringup.Bringup(report, run)
    assert got.spawn_s == 2.5 and got.backend_start_s == 10.0
    assert got.device_touch_s == 5.5  # rank 1: 3.0 + 2.5; rank 0: 2.0 + 3.0
    assert got.gang_join_s == 4.0 and got.session_start_s == 1.0
    # 15.5 s less placement 0.5, spawn 2.0, backend 10.0, session -> entered 1.0
    assert got.unaccounted_s == pytest.approx(2.0)
    assert got.spawn_s + got.backend_start_s + got.session_start_s + got.unaccounted_s == pytest.approx(15.5)
    assert got.timeline()["worker.import_jax"] == [2.0, 3.0] and got.timeline()["spawn"] == [2.0, 2.0]
    assert got.top_functions() == [["f", 2.0, 7, 1, 2.0]]
