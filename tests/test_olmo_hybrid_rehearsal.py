"""The Olmo-Hybrid cell's CPU rehearsal: `benchmark/run.py --rehearse-cpu`, four workers, `fsdp=4`, the nano sizes,
the whole path of a chip run. A file of its own: a minute and a half, one worker's."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "olmo-hybrid-7b-fsdp4"
CELL = CONFIG + ".fed4k"


def test_the_cells_cpu_rehearsal_prints_the_contracts_line():
    # A window of 4 s: four worker processes beside the suite's six take a second a step (PR 68's whole run counted 2
    # steps in 2 s where the file alone counts 4, on the parent's tree too), and the line wants more than two.
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "2147493039",
         "--seconds", "4", "--trace", "1", "--rehearse-cpu"],
        cwd=REPO, capture_output=True, text=True, timeout=400)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 2
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 4 and "platform=cpu" in proc.stdout
    assert all(name.startswith("rehearsal.") for name in line["metrics"])
    for name in ("data.wait_ms", "host.h2d_ms", "host.report_ms", "host.report_put_ms", "entry.gang_join_s"):
        assert line["metrics"][f"rehearsal.{name}.{CONFIG}"]["value"] is not None, name
    # The scan's readings come off the device's trace, and the CPU's holds no device operation.
    assert not [name for name in line["metrics"] if ".gdn" in name]
    assert '"gdn.neg_eigval_share": ' in proc.stdout and '"gdn.decay_min": ' in proc.stdout
    assert "'fsdp': 4" in proc.stdout or '"fsdp": 4' in proc.stdout
