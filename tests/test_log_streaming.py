"""Pubsub channels + worker log/error streaming to the driver (reference:
`python/ray/_private/log_monitor.py:104` tailing worker logs into GCS
pubsub, `src/ray/pubsub/publisher.h`; VERDICT r3 ask #7)."""

import sys
import time

import pytest

import ray_tpu


@pytest.fixture
def published(ray_start_regular):
    """Every payload the scheduler publishes on the `logs` and `errors`
    channels from here on, in order. The driver's own printer subscribed
    before `init()` returned, so a payload seen here has been printed."""
    from ray_tpu._private import worker as worker_mod

    seen = []
    sched = worker_mod.global_worker.context.scheduler
    for channel in ("logs", "errors"):
        sched.call("subscribe", (channel, seen.append)).result()
    return seen


def _drain_until(capfd, needle: str, published, timeout: float = 20.0) -> str:
    """What the driver printed, once `needle` has been published (log pushes
    are asynchronous w.r.t. task completion). Read once, after the line
    looked for: `readouterr()` reads the capture file and then truncates it,
    and a line that the scheduler's thread writes between the two is gone for
    good, which under load lost a print's first batch of lines one run in six
    (PR 46: "alpha" and "beta" published and printed, "gamma" alone read)."""
    deadline = time.time() + timeout
    while time.time() < deadline and needle not in str(published):
        time.sleep(0.05)
    return "".join(capfd.readouterr())


def test_remote_print_reaches_driver(ray_start_regular, published, capfd):
    """The VERDICT done-criterion: a remote task's print arrives at the
    driver, prefixed with the task name and worker pid."""

    @ray_tpu.remote
    def chatty():
        print("hello from the worker side")
        return 1

    assert ray_tpu.get(chatty.remote(), timeout=60) == 1
    acc = _drain_until(capfd, "hello from the worker side", published)
    assert "hello from the worker side" in acc
    # Prefix carries the task name and a pid.
    line = next(l for l in acc.splitlines() if "hello from the worker side" in l)
    assert "chatty" in line and "pid=" in line


def test_actor_stderr_reaches_driver(ray_start_regular, published, capfd):
    @ray_tpu.remote
    class Noisy:
        def speak(self):
            sys.stderr.write("actor stderr line\n")
            return "ok"

    a = Noisy.remote()
    assert ray_tpu.get(a.speak.remote(), timeout=60) == "ok"
    acc = _drain_until(capfd, "actor stderr line", published)
    assert "actor stderr line" in acc


def test_worker_crash_pushes_error_channel(ray_start_regular, published, capfd):
    """Terminal worker-death errors reach the driver's stderr even before
    anyone get()s the failed ref (the errors channel)."""

    @ray_tpu.remote(max_retries=0)
    def die():
        import os

        os._exit(1)

    ref = die.remote()
    with pytest.raises(ray_tpu.exceptions.WorkerCrashedError):
        ray_tpu.get(ref, timeout=60)
    acc = _drain_until(capfd, "WorkerCrashedError", published)
    assert "die" in acc


def test_log_to_driver_false_suppresses(tmp_path, capfd):
    ray_tpu.init(num_cpus=2, log_to_driver=False)
    try:
        @ray_tpu.remote
        def quiet_chatty():
            print("this must stay in the worker log")
            return 1

        assert ray_tpu.get(quiet_chatty.remote(), timeout=60) == 1
        time.sleep(1.0)
        out = capfd.readouterr()
        assert "this must stay in the worker log" not in out.err + out.out
    finally:
        ray_tpu.shutdown()


def test_custom_pubsub_channel_inproc(ray_start_regular):
    """The generalized channel seam: subscribe a callback, publish from the
    scheduler, observe delivery (the substrate logs/errors ride on)."""
    from ray_tpu._private import worker as worker_mod

    sched = worker_mod.global_worker.context.scheduler
    got = []
    sched.call("subscribe", ("custom", got.append)).result()
    sched._publish("custom", {"x": 1})  # direct: runs on caller thread
    deadline = time.time() + 10
    while not got and time.time() < deadline:
        time.sleep(0.05)
    assert got == [{"x": 1}]


def test_multiline_and_flush_batching(ray_start_regular, published, capfd):
    @ray_tpu.remote
    def multi():
        print("alpha\nbeta\ngamma")
        return 1

    ray_tpu.get(multi.remote(), timeout=60)
    acc = _drain_until(capfd, "gamma", published)
    for word in ("alpha", "beta", "gamma"):
        assert word in acc


def test_the_first_lines_of_a_fresh_runtime_reach_the_driver(capfd):
    """`init()` returns with the driver's printer subscribed to `logs`: a task
    that prints the moment the runtime is up loses no line, three runtimes
    in a row, and the lines come in the order they were printed."""
    from ray_tpu._private import worker as worker_mod

    for n in range(3):
        ray_tpu.init(num_cpus=2)
        try:
            @ray_tpu.remote
            def early(n):
                print(f"first of {n}\nsecond of {n}\nthird of {n}")
                return n

            seen = []
            worker_mod.global_worker.context.scheduler.call("subscribe", ("logs", seen.append)).result()
            assert ray_tpu.get(early.remote(n), timeout=60) == n
            acc = _drain_until(capfd, f"third of {n}", seen)
            at = [acc.find(f"{word} of {n}") for word in ("first", "second", "third")]
            assert -1 not in at and at == sorted(at), acc
        finally:
            ray_tpu.shutdown()

