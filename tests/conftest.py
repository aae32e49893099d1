"""Shared fixtures, modeled on the reference's `python/ray/tests/conftest.py`
(`ray_start_regular:313`, `ray_start_cluster:394`).

JAX-dependent tests run on a virtual 8-device CPU mesh: the env vars must be set
before jax initializes its backends (SURVEY.md §7 / task instructions), so they are
set at conftest import time, before any test module imports jax.
"""

import os
import sys

# Tests run on the virtual 8-device CPU mesh whatever the machine holds: the
# env is set for worker subprocesses, and the jax config is pinned in this
# process before any backend initializes.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
if "xla_cpu_collective_call_terminate_timeout_seconds" not in flags:
    # The virtual 8-device mesh time-shares this box's core(s): all 8 device
    # programs' pre-collective compute serializes, so a heavy first step
    # (conv grads compiling + executing) can exceed XLA CPU's default 40s
    # collective-rendezvous kill switch, which hard-aborts the process
    # (rendezvous.cc "Termination timeout ... Exiting"). Raise warn/terminate
    # far above any legitimate single-step skew; a true deadlock still dies,
    # just slower. (Flags of the installed jaxlib 0.9.0; XLA aborts the
    # process on a flag it does not know.)
    flags += (
        " --xla_cpu_collective_call_warn_stuck_timeout_seconds=120"
        " --xla_cpu_collective_call_terminate_timeout_seconds=600"
    )
os.environ["XLA_FLAGS"] = flags

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402

import ray_tpu  # noqa: E402


import contextlib  # noqa: E402
import faulthandler  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402

# What a test's set-up, its call and its teardown may each take. The driver's command gives the whole
# run one clock; without a limit of its own a test that hangs takes the run, and every test behind it,
# with it. (`tests/aot_v5e.py CASE_LIMIT_S`, what a test may wait for an ahead-of-time compile, stands
# under this so that a slow compile is named first; `tests/benchmark/`'s one test of 185-220 s under the
# suite's own load, PR 46, carries a `timeout=300` of its own.)
TEST_LIMIT_S = 300.0


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: left out of tier-1 (`-m 'not slow'`): minutes of compiles")


def _limited(item, phase):
    """Fail `item` alone, by its name and with every thread's stack, when
    `phase` of it passes `TEST_LIMIT_S`. SIGALRM reaches Python between two
    bytecodes of the main thread: it bounds waits, sleeps, joins and
    subprocesses, which is what hangs here, not a native call that never
    returns (an XLA compile runs to its end first). Worker threads and
    platforms without `setitimer` run unlimited."""
    def passed(signum, frame):
        faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        pytest.fail(f"{item.nodeid}: {phase} passed its limit of {TEST_LIMIT_S:g} s (stacks above)", pytrace=False)

    before = signal.signal(signal.SIGALRM, passed)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    return (yield from _limited(item, "set-up"))


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    return (yield from _limited(item, "call"))


@pytest.hookimpl(wrapper=True)
def pytest_runtest_teardown(item):
    return (yield from _limited(item, "teardown"))


def kill_and_sweep(proc, timeout=15):
    """SIGKILL `proc` (a head, a driver) and remove what it then cannot: its
    `/dev/shm/ray_tpu_{head,session}_<pid>_*` directory. A test that kills a
    process on purpose owns what the process leaves (92 such directories stood
    on one machine at PR 40's anchor)."""
    import glob
    import shutil

    proc.kill()
    proc.wait(timeout=timeout)
    for left in glob.glob(f"/dev/shm/ray_tpu_*_{proc.pid}_*"):
        shutil.rmtree(left, ignore_errors=True)


@contextlib.contextmanager
def head_process_runtime(num_cpus=4):
    """Out-of-process control plane: spawn a head server (`_private/head.py`)
    and connect this process as a client driver over TCP."""
    from ray_tpu._private.launch import spawn_head

    proc, info = spawn_head(num_cpus=num_cpus, num_tpus=0, timeout_s=60)
    old_key = os.environ.get("RAY_TPU_AUTHKEY_HEX")
    os.environ["RAY_TPU_AUTHKEY_HEX"] = info["authkey_hex"]
    try:
        ctx = ray_tpu.init(address=info["address"])
        yield ctx
    finally:
        ray_tpu.shutdown()
        if old_key is None:
            os.environ.pop("RAY_TPU_AUTHKEY_HEX", None)
        else:
            os.environ["RAY_TPU_AUTHKEY_HEX"] = old_key
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            kill_and_sweep(proc)


@pytest.fixture
def ray_start_regular():
    """A 4-CPU single-node runtime, torn down after the test."""
    ctx = ray_tpu.init(num_cpus=4, ignore_reinit_error=False)
    yield ctx
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    """Multi-virtual-node cluster builder (reference: `cluster_utils.Cluster`)."""
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(head_node_args={"num_cpus": 1})
    yield cluster
    cluster.shutdown()
