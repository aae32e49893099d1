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
import json  # noqa: E402
import subprocess  # noqa: E402


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
            proc.kill()


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
