"""The seams of the test suite itself (PR 46): one ahead-of-time harness, a time limit a case and a test."""

import os
import re
import subprocess
import sys
import time

import pytest

import aot_v5e

TESTS = os.path.dirname(os.path.abspath(__file__))


def test_no_test_file_describes_a_topology_or_compiles_ahead_of_time_by_itself():
    """Outside `tests/benchmark/` (the benchmark's own), `tests/aot_v5e.py` alone asks libtpu for a `v5e:2x2`
    and alone runs itself as a subprocess to compile for it; every other file asks it for cases."""
    asks, runs_itself = [], []  # (the name in two halves, so that a grep for it finds the harness alone)
    for name in sorted(os.listdir(TESTS)):
        if name.endswith(".py") and name not in ("aot_v5e.py", os.path.basename(__file__)):
            with open(os.path.join(TESTS, name)) as fh:
                text = fh.read()
            if "get_topology" + "_desc" in text or "jax.experimental import topologies" in text:
                asks.append(name)
            if re.search(r"subprocess\.\w+\(\s*\[sys\.executable, (os\.path\.abspath\()?__file__", text):
                runs_itself.append(name)
    assert asks == [] and runs_itself == []
    # ... and no file asks for every cell's whole step: the harness hands a file two at most.
    with pytest.raises(AssertionError):
        aot_v5e.steps("gpt2-medium", "gpt2-xl-fsdp4", "olmoe-1b-7b-l1")


def test_a_case_past_its_limit_fails_by_its_name_and_the_cases_behind_it_pass(monkeypatch):
    cases = aot_v5e.Cases(["kernel", "lower:d4"], limits={"kernel": 0.0})
    assert cases["lower:d4"]["mosaic_calls"] == 2 and cases[aot_v5e.TOPOLOGY]["device_kind"] == "TPU v5 lite"
    with pytest.raises(pytest.fail.Exception, match="ahead-of-time case kernel: passed its limit of 0 s"):
        cases["kernel"]
    # A case that raises says so by its name too, and takes nothing with it.
    cases = aot_v5e.Cases(["no_such_case", "lower:d2t2"])
    # (A read that its list outlasts fails by the name of the case not reached, and the next read goes on.)
    monkeypatch.setattr(aot_v5e, "READ_LIMIT_S", 0.0)
    with pytest.raises(pytest.fail.Exception, match="ahead-of-time case lower:d2t2: not reached in the 0 s"):
        cases["lower:d2t2"]
    monkeypatch.undo()
    assert cases["lower:d2t2"]["mosaic_calls"] == 2
    with pytest.raises(pytest.fail.Exception, match=r"(?s)ahead-of-time case no_such_case: .*no such case"):
        cases["no_such_case"]


def test_a_test_that_sleeps_past_the_limit_fails_alone_with_its_stacks_and_the_run_goes_on():
    start = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", os.path.join(TESTS, "a_test_that_sleeps.py"), "-q", "-p", "no:cacheprovider"],
        capture_output=True, text=True, timeout=200, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert time.monotonic() - start < 50  # not the minute it meant to sleep
    assert "1 failed, 1 passed" in proc.stdout, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert re.search(r"a_test_that_sleeps\.py::test_sleeps_past_the_limit: call passed its limit of 1 s", proc.stdout)
    # `faulthandler`'s dump of every thread, the sleeping line among them.
    assert "most recent call first" in proc.stderr + proc.stdout and "test_sleeps_past_the_limit" in proc.stderr + proc.stdout


def test_slow_is_a_registered_mark(pytestconfig):
    assert any(line.startswith("slow:") for line in pytestconfig.getini("markers"))
