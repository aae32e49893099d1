"""The Trinity cell's CPU rehearsal: `benchmark/run.py --rehearse-cpu`, one worker, the nano sizes, the whole path of a
chip run (`JaxTrainer.fit()` -> `create_train_state` / `make_train_step` with the buffer's rule inside the jitted step,
the fed loop, the reference check, a traced window). A file of its own: a minute, one worker's."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "trinity-mini-ep16-l5"
CELL = CONFIG + ".fed16k"


def test_the_cells_cpu_rehearsal_prints_the_contracts_line():
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "2147493039",
         "--seconds", "2", "--trace", "1", "--rehearse-cpu"],
        cwd=REPO, capture_output=True, text=True, timeout=400)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 2
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1 and "platform=cpu" in proc.stdout
    assert all(name.startswith("rehearsal.") for name in line["metrics"])
    for name in ("moe.load_max_over_mean",):
        assert line["metrics"][f"rehearsal.{name}.{CONFIG}"]["value"] is not None, name
    # The window's readings come off the device's trace, and the CPU's holds no device operation.
    assert not [name for name in line["metrics"] if "window" in name or ".swa." in name]
    # Each limit beside its reading, leaf by leaf, in the line's last key; and the buffer's two.
    compared = line["compared"]
    assert len([name for name in compared if name.startswith("leaf_grad_rel_err.")]) == 15
    assert compared["expert_choices_flipped_share"][0] <= compared["expert_choices_flipped_share"][1]
    assert compared["bias_rule_abs_err"][0] <= compared["bias_rule_abs_err"][1] == 1e-6
    assert compared["bias_abs_err_far"][0] <= compared["bias_abs_err_far"][1]
    assert compared["check"]["routing"]["dropped"] == 0 and compared["check"]["bias"]["abs_max_after"] > 0
    assert compared["check"]["over_limit"] == []
