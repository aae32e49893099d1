"""Live cluster introspection: on-demand stack dumps (in-band + SIGUSR1
out-of-band), the cluster-wide sampling profiler, memory/ownership
attribution with leak suspects, heartbeat flight recorders, and knob-off
parity.

Reference surfaces: `ray stack` (py-spy over every worker), `ray memory`
(core-worker ownership tables), per-worker profiling. See COMPONENTS.md
"Introspection".
"""

import os
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import exceptions
from ray_tpu.util import state


def _spin_remote():
    @ray_tpu.remote
    def spin(sec):
        t0 = time.time()
        x = 0
        while time.time() - t0 < sec:
            x += 1
        return x

    return spin


# ----------------------------------------------------------- stack dumps
def test_stack_dump_busy_spin_annotated(ray_start_regular):
    """state.stacks() on a cluster running a busy-spin task returns, for the
    executing worker, a thread annotated with the task name whose stack
    shows the spin function — while the task is still running."""
    spin = _spin_remote()
    ref = spin.remote(8.0)
    hit = None
    dumps = {}
    deadline = time.time() + 20
    while time.time() < deadline and hit is None:
        dumps = state.stacks()
        for key, payload in dumps.items():
            if not key.startswith("worker:"):
                continue
            for th in payload.get("threads", ()):
                if th.get("task") == "spin" and any(
                    f.startswith("spin ") for f in th.get("frames", ())
                ):
                    hit = (key, th)
        if hit is None:
            time.sleep(0.2)
    assert hit is not None, dumps
    key, th = hit
    assert "spin" in th["stack"]
    # The head (control plane) dumps itself too, with its scheduler thread.
    head = dumps["head"]
    assert head["transport"] == "inband"
    assert any(t["name"] == "scheduler" for t in head["threads"])
    # Worker payloads carry their identity and the current task.
    assert dumps[key]["role"] == "worker"
    assert dumps[key]["current_task"] == "spin"
    assert isinstance(ray_tpu.get(ref, timeout=60), int)


def test_stack_dump_oob_when_reader_wedged():
    """A worker whose reader thread cannot answer (conn.recv delayed past
    the in-band deadline) is escalated to the out-of-band path: SIGUSR1
    fires its registered faulthandler and the dump tails back with
    transport="oob"."""
    os.environ["RAY_TPU_FAILPOINTS"] = "conn.recv=delay:8@always"
    os.environ["RAY_TPU_introspection_timeout_s"] = "1.5"
    try:
        ray_tpu.init(num_cpus=1)

        @ray_tpu.remote
        def noop():
            return 1

        assert ray_tpu.get(noop.remote(), timeout=60) == 1
        dumps = state.stacks()
        workers = {k: v for k, v in dumps.items() if k.startswith("worker:")}
        assert workers
        payload = next(iter(workers.values()))
        assert payload["transport"] == "oob", payload
        # faulthandler's formatted output, not ours: "Thread 0x...".
        assert "Thread" in payload["raw"] and "File" in payload["raw"]
    finally:
        os.environ.pop("RAY_TPU_FAILPOINTS", None)
        os.environ.pop("RAY_TPU_introspection_timeout_s", None)
        ray_tpu.shutdown()


# ---------------------------------------------------------------- profiler
def test_profile_merges_folded_stacks_across_workers(ray_start_regular):
    """state.profile() over two concurrently spinning workers returns merged
    folded stacks in which the spin function dominates, attributed to >= 2
    distinct worker processes; the chrome rendering merges into timeline()."""
    spin = _spin_remote()
    refs = [spin.remote(6.0) for _ in range(2)]
    time.sleep(1.0)  # both attempts executing
    res = state.profile(1.5, hz=200)
    folded = res["folded"]
    assert res["samples"] > 0
    spin_keys = [
        k for k in folded if k.startswith("worker:") and ";spin " in k
    ]
    assert len({k.split(";")[0] for k in spin_keys}) >= 2, folded
    # Dominance: among worker MainThread samples (the task-executing
    # thread), the spin frames take the majority.
    main = {
        k: v for k, v in folded.items()
        if k.startswith("worker:") and ";MainThread;" in k
    }
    spin_samples = sum(v for k, v in main.items() if ";spin " in k)
    assert spin_samples > 0.5 * sum(main.values()), main
    # flamegraph.pl input: "stack count" lines.
    line = res["flamegraph"].splitlines()[0]
    assert line.rsplit(" ", 1)[1].isdigit()
    assert ray_tpu.get(refs, timeout=60)
    trace = ray_tpu.timeline()
    prof_events = [e for e in trace if e.get("cat") == "profile"]
    assert prof_events and all("ts" in e and e["dur"] >= 1 for e in prof_events)


def test_profiler_knob_off_parity():
    """enable_profiler=False: state.profile errors, no profile message is
    ever broadcast, and no process grows a sampler thread."""
    ray_tpu.init(num_cpus=2, _system_config={"enable_profiler": False})
    try:
        with pytest.raises(RuntimeError, match="disabled"):
            state.profile(0.1)

        @ray_tpu.remote
        def worker_threads():
            return sorted(t.name for t in threading.enumerate())

        names = ray_tpu.get(worker_threads.remote(), timeout=60)
        assert not any("profiler" in n for n in names), names
        assert not any(
            "profiler" in t.name for t in threading.enumerate()
        )
        from ray_tpu._private.worker import global_worker

        sched = global_worker.context.scheduler
        # No profile session started, no fan-out in flight: the disabled
        # knob produced zero new protocol traffic.
        assert sched.telemetry.profile_sessions == 0
        assert sched._introspect_pending == {}
    finally:
        ray_tpu.shutdown()


# ----------------------------------------------------------- memory summary
def test_memory_summary_accounting_and_dead_holder_suspect():
    ray_tpu.init(num_cpus=2, _system_config={"use_native_object_arena": False})
    try:
        refs = [ray_tpu.put(np.zeros(40_000)) for _ in range(4)]
        summary = state.memory_summary()
        # Per-object accounting reconciles with the object-store gauge
        # (ray_tpu_object_store_bytes == sum(node_usage)) to >= 95%.
        assert summary["gauge_bytes"] > 0
        assert summary["shm_bytes"] >= 0.95 * summary["gauge_bytes"]
        assert summary["num_objects"] >= 4
        assert not summary["leak_suspects"]
        site_bytes = sum(a["bytes"] for a in summary["by_site"].values())
        assert site_bytes >= summary["shm_bytes"]

        # An object whose ONLY reference lives on a dead process: register a
        # borrower under a holder id no live process owns, then drop the
        # driver's ref. The mark-sweep must flag it.
        suspect_hex = refs[0].hex()
        suspect_key = refs[0].binary()
        from ray_tpu._private.worker import flush_ref_ops, global_worker

        sched = global_worker.context.scheduler
        sched.call("ref_ops", ([("add", suspect_key)], "deadbeefdeadbeef")).result()
        del refs
        flush_ref_ops()
        time.sleep(0.3)
        summary = state.memory_summary()
        suspects = {o["object_id"]: o for o in summary["leak_suspects"]}
        assert suspect_hex in suspects, summary["leak_suspects"]
        assert suspects[suspect_hex]["holders"] == ["deadbeefdeadbeef"]
    finally:
        ray_tpu.shutdown()


def test_memory_summary_flags_bytes_orphaned_by_owner_crash():
    """worker.crash_before_result_stored kills the owner AFTER its result
    bytes hit the store but before the done message: nothing ever frees
    those bytes, and the store scan must flag them."""
    ray_tpu.init(num_cpus=2, _system_config={"use_native_object_arena": False})
    try:
        baseline = state.memory_summary()["store_scan"]["leaked_bytes"]
        os.environ["RAY_TPU_FAILPOINTS"] = (
            "worker.crash_before_result_stored=crash@once"
        )
        try:

            @ray_tpu.remote(max_retries=0)
            def make_big():
                return np.zeros(100_000)

            with pytest.raises(exceptions.WorkerCrashedError):
                ray_tpu.get(make_big.remote(), timeout=60)
        finally:
            os.environ.pop("RAY_TPU_FAILPOINTS", None)
        summary = state.memory_summary()
        scan = summary["store_scan"]
        leaked = scan["leaked_bytes"] - baseline
        assert leaked >= 100_000 * 8, scan
        assert any(e["bytes"] >= 100_000 * 8 for e in scan["leaked"]), scan
    finally:
        ray_tpu.shutdown()


# ----------------------------------------------------- flight recorder
def test_flight_recorder_captured_on_worker_suspect():
    """The heartbeat detector auto-captures a stack dump the moment a worker
    goes SUSPECT (beats silenced by failpoint, process otherwise healthy),
    and list_nodes() surfaces it on the worker entry."""
    os.environ["RAY_TPU_health_check_period_ms"] = "200"
    os.environ["RAY_TPU_FAILPOINTS"] = "worker.heartbeat=drop@always"
    try:
        ray_tpu.init(num_cpus=1)

        @ray_tpu.remote
        def noop():
            return 1

        assert ray_tpu.get(noop.remote(), timeout=60) == 1
        found = None
        deadline = time.time() + 25
        while time.time() < deadline and found is None:
            for n in state.list_nodes():
                for w in n.get("workers", ()):
                    if w["health"] == "SUSPECT" and w.get("flight_recorder"):
                        found = w
            if found is None:
                time.sleep(0.1)
        assert found is not None, "no flight recorder captured"
        fr = found["flight_recorder"]
        assert fr["trigger"] == "SUSPECT"
        # The worker is only beat-silenced, not wedged: the in-band dump
        # succeeded and shows its real threads.
        dump = fr["dump"]
        assert dump["transport"] == "inband"
        assert any(t["name"] == "reader" for t in dump["threads"])
    finally:
        os.environ.pop("RAY_TPU_FAILPOINTS", None)
        os.environ.pop("RAY_TPU_health_check_period_ms", None)
        ray_tpu.shutdown()


def test_flight_recorder_never_signals_the_worker_it_looks_at():
    """A SUSPECT worker that misses the in-band deadline as well (a long
    native call holding the GIL: here its reader is delayed) is recorded
    "unavailable". The capture nobody asked for does not escalate to SIGUSR1:
    the faulthandler dump walks running threads' frames from a signal handler
    and has killed workers mid-compile. The worker's stack file stays empty,
    and an explicit request still takes the out-of-band path."""
    os.environ["RAY_TPU_health_check_period_ms"] = "200"
    os.environ["RAY_TPU_introspection_timeout_s"] = "1.0"
    os.environ["RAY_TPU_FAILPOINTS"] = (
        "worker.heartbeat=drop@always;conn.recv=delay:6@always")
    try:
        ray_tpu.init(num_cpus=1)

        @ray_tpu.remote
        def noop():
            return 1

        assert ray_tpu.get(noop.remote(), timeout=60) == 1
        found = None
        deadline = time.time() + 25
        while time.time() < deadline and found is None:
            for n in state.list_nodes():
                for w in n.get("workers", ()):
                    if w.get("flight_recorder"):
                        found = w
            if found is None:
                time.sleep(0.1)
        assert found is not None, "no flight recorder captured"
        assert found["flight_recorder"]["dump"]["transport"] == "unavailable"
        assert ray_tpu.get(noop.remote(), timeout=60) == 1  # and it lives
        workers = {k: v for k, v in state.stacks().items()
                   if k.startswith("worker:")}
        assert [v["transport"] for v in workers.values()] == ["oob"]
    finally:
        os.environ.pop("RAY_TPU_FAILPOINTS", None)
        os.environ.pop("RAY_TPU_health_check_period_ms", None)
        os.environ.pop("RAY_TPU_introspection_timeout_s", None)
        ray_tpu.shutdown()


# ------------------------------------------------------ log-drop satellite
def test_log_shipper_drop_counter_exported(ray_start_regular):
    """_LogShipper overflow increments the module counter that
    ensure_logshipper_metrics exports as ray_tpu_log_lines_dropped_total
    (previously only a '...dropped' text line)."""
    from ray_tpu._private import telemetry, worker_main
    from ray_tpu.util import metrics as metrics_api

    class _StuckConn:
        def send(self, msg):
            raise AssertionError("drain must not run in this test")

    before = worker_main._LOG_STATS["dropped"]
    shipper = worker_main._LogShipper.__new__(worker_main._LogShipper)
    import collections

    shipper._wc = _StuckConn()
    shipper._worker_id_hex = "test"
    shipper._q = collections.deque()
    shipper._dropped = 0
    shipper._event = threading.Event()  # no drain thread: queue only fills
    for i in range(worker_main._LogShipper.MAX_LINES + 5):
        shipper.enqueue("stdout", "t", [f"line {i}"])
    assert worker_main._LOG_STATS["dropped"] - before == 5
    assert shipper._dropped == 5

    telemetry.ensure_logshipper_metrics()
    text = metrics_api.prometheus_text()
    assert "ray_tpu_log_lines_dropped_total" in text
    value = [
        line for line in text.splitlines()
        if line.startswith("ray_tpu_log_lines_dropped_total ")
    ]
    assert value and float(value[0].rsplit(" ", 1)[1]) >= 5
