"""`run.py --rehearse-cpu` end to end, through `ray_tpu.init()` and
`JaxTrainer.fit()`, for both loops; and what an untraced run says of its
set-up (ISSUE 56): the three numbers and the program's bring-up line."""

import functools
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}
SEED = 3


@functools.lru_cache(maxsize=None)
def rehearsed(cell, trace=0):
    """One untraced CPU rehearsal a cell, however many tests read it: the process, and the record it left."""
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed", str(SEED), "--seconds", "2",
         "--trace", str(trace), "--rehearse-cpu"],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    with open(os.path.join(REPO, "benchmark", "out", f"{cell}.{SEED}.json")) as fh:
        return proc, json.load(fh)


@pytest.mark.parametrize("cell,trace", [("gpt2-medium.resident", 0), ("gpt2-medium.fed", 0)])
def test_rehearsal_prints_the_contracts_line_and_says_cpu(cell, trace):
    proc, _ = rehearsed(cell, trace)
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(line) == CONTRACT_KEYS
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 3
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    # A CPU number never stands under a device metric's name.
    assert set(line["metrics"]) == {"rehearsal.tokens_per_s_per_chip", "rehearsal.setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert "platform=cpu" in proc.stdout


def test_a_rehearsals_setup_s_subtracts_nothing_and_the_record_holds_the_three_numbers():
    """A lone CPU worker opens no `device_touch` span: `rehearsal.setup_s` is the whole interval, process start to
    the first timed step, and `out/<cell>.<seed>.json` and the `[run]` set-up line carry the three all the same."""
    proc, record = rehearsed("gpt2-medium.resident")
    setup = record["setup"]
    assert set(setup) == {"whole_s", "chip_open_s", "setup_s"}
    assert setup["chip_open_s"] == 0.0 and setup["setup_s"] == setup["whole_s"] > 0
    assert setup["whole_s"] == record["summary"]["window_wall_start"] - record["parent"]["t_start_wall"]
    assert record["line"]["metrics"]["rehearsal.setup_s"] == {"value": setup["setup_s"], "unit": "s"}
    assert "worker.device_touch" not in record["summary"]["bringup"]["timeline_s"]
    said = next(line for line in proc.stdout.splitlines() if line.startswith("[run] set-up: "))
    assert (f"process start -> first timed step {setup['whole_s']:.2f}s less the chip's open 0.00s = setup_s "
            f"{setup['setup_s']:.2f}s") in said


@pytest.mark.parametrize("cell", ["gpt2-medium.resident", "gpt2-medium.fed"])
def test_the_untraced_run_prints_the_bring_up_line(cell):
    """`setup_s` is read off the program's own spans, so every run finds its report: the bring-up and compile
    lines were a traced run's alone until PR 56, and the untraced record keeps the timeline too."""
    proc, record = rehearsed(cell)
    assert record["trace"] == 0
    assert proc.stdout.count("[run] bring-up s by span [rank 0, slowest rank]") == 1
    assert proc.stdout.count("[run] compile counter, rank 0 over the fit()") == 1
    assert record["summary"]["bringup"]["timeline_s"]["worker.import_jax"][1] > 0
    assert "bringup" not in record  # the `Bringup` itself is the readers', not the record's


# ---- every number `correct` is decided from, beside its limit (the line's last key and standard error's last line)
def _summary(check, completed=5, attempted=5, failed=0, compiles=0, mosaic=17):
    return {"check": check, "completed": completed, "attempted": attempted, "failed": failed,
            "compiles_in_window": {"count": compiles}, "compiled_step": {"mosaic_calls": mosaic}}


def test_a_limit_named_as_its_reading_stands_beside_it_leaf_by_leaf():
    from benchmark.harness import driver

    check = {"loss_abs_err": 3e-3, "grad_norm_rel_err": 0.11, "leaf_grad_norm_rel_err": {"wk": 0.059, "w_a": 2e-3},
             "loss_system": 12.27, "ok": False,
             "limits": {"loss_abs_err": 6e-3, "grad_norm_rel_err": 1e-2, "leaf_grad_norm_rel_err": {"wk": 2e-2, "w_a": 2e-2}}}
    out = driver.compared(_summary(check), rehearse=False)
    assert out["loss_abs_err"] == [3e-3, 6e-3] and out["grad_norm_rel_err"] == [0.11, 1e-2]
    assert out["leaf_grad_norm_rel_err.wk"] == [0.059, 2e-2] and out["leaf_grad_norm_rel_err.w_a"] == [2e-3, 2e-2]
    # What is left of the check follows as it is; a limit that was paired is not said twice.
    assert out["check"] == {"loss_system": 12.27, "ok": False} and "limits" not in out
    assert check["limits"]["loss_abs_err"] == 6e-3  # the summary is not changed under the readers' feet


def test_limits_under_other_names_follow_as_they_are_and_the_windows_own_come_last():
    from benchmark.harness import driver

    check = {"loss_abs_err": 1e-4, "ok": True, "limits": {"LOSS_ABS_TOL": 1e-3, "loss_abs": 0.03}}
    out = driver.compared(_summary(check, completed=4, attempted=5, failed=1, compiles=2, mosaic=1), rehearse=False)
    assert out["check"] == {"loss_abs_err": 1e-4, "ok": True}
    assert out["limits"] == {"LOSS_ABS_TOL": 1e-3, "loss_abs": 0.03}
    assert out["steps_completed_of_dispatched"] == [4, 5] and out["steps_outside_loss_band"] == [1, 0]
    assert out["compiles_in_window"] == [2, 0] and out["mosaic_calls_at_least"] == [1, 2]
    assert driver.compared(_summary({"ok": True}, mosaic=0), rehearse=True)["mosaic_calls_at_least"] == [0, 0]


@pytest.mark.parametrize("cell", ["gpt2-medium.resident", "gpt2-medium.fed"])
def test_the_lines_last_key_and_standard_errors_last_line_hold_each_number_compared(cell):
    proc, record = rehearsed(cell)
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "compared"
    said = proc.stderr.strip().splitlines()[-1]
    assert said.startswith("compared, [reading, limit]: ") and json.loads(said.split(": ", 1)[1]) == line["compared"]
    compared = line["compared"]
    # GPT-2's module names its limits `*_TOL`: the worker brings them where the model's `check` gives none.
    assert compared["limits"] == {"LOSS_ABS_TOL": 1e-3, "GRAD_NORM_REL_TOL": 1e-2}
    assert compared["check"]["loss_abs_err"] <= 1e-3 and compared["check"]["ok"] is True
    assert compared["steps_completed_of_dispatched"] == [line["attempted"]] * 2
    assert compared["compiles_in_window"] == [0, 0] and compared["trace_read_if_asked"] == [False, False]
    # The timed step's own first loss and gradient norm, beside the check's.
    first = compared["check"]["first_step"]
    assert set(first) == {"loss", "grad_norm"} and first["grad_norm"] > 0
    assert record["summary"]["check"]["first_step"] == first
