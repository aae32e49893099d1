"""`run.py --rehearse-cpu` end to end, through `ray_tpu.init()` and
`JaxTrainer.fit()`, for both loops."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("cell,trace", [("gpt2-medium.resident", 0), ("gpt2-medium.fed", 0)])
def test_rehearsal_prints_the_contracts_line_and_says_cpu(cell, trace):
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed", "3", "--seconds", "2",
         "--trace", str(trace), "--rehearse-cpu"],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
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
