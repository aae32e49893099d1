"""The Xing4.0 cell's CPU rehearsal: `benchmark/run.py --rehearse-cpu`, one worker, the nano sizes, the whole path of a
chip run (`JaxTrainer.fit()` -> `create_train_state` / `make_train_step` over four streams, the fed loop, the reference
check of the bf16 program under the toy's limits, a traced window). A file of its own: a minute, one worker's."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "xing4-29b-a4b-ep8-l5"
CELL = CONFIG + ".fed4k"


def test_the_cells_cpu_rehearsal_prints_the_contracts_line():
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "2147493066",
         "--seconds", "2", "--trace", "1", "--rehearse-cpu"],
        cwd=REPO, capture_output=True, text=True, timeout=400)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 2
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1 and "platform=cpu" in proc.stdout
    assert all(name.startswith("rehearsal.") for name in line["metrics"])
    assert line["metrics"][f"rehearsal.moe.load_max_over_mean.{CONFIG}"]["value"] is not None
    assert 0 < line["metrics"]["rehearsal.mhc.res_sum_err"]["value"] < 0.05
    # The streams' times come off the device's trace, and the CPU's holds no device operation.
    assert not [name for name in line["metrics"] if name.startswith("rehearsal.mhc.") and name.endswith(("_ms", "_roofline"))]
    # Each limit beside its reading, leaf by leaf, in the line's last key.
    compared = line["compared"]
    assert len([name for name in compared if name.startswith("leaf_grad_rel_err.")]) == 10
    assert compared["expert_choices_flipped_share"][0] <= compared["expert_choices_flipped_share"][1]
    assert compared["res_sum_err"][0] <= compared["res_sum_err"][1]
    assert compared["check"]["routing"]["dropped"] == 0 and compared["check"]["over_limit"] == []
    assert compared["check"]["streams"]["streams"] == 4 and compared["check"]["streams"]["rounds"] == 20
