"""Head-restart recovery beyond detached actors: jobs fail with a queryable
record and named OWNED actors come back reachable (reference:
`gcs_actor_manager.h:281` actor-table recovery, GcsJobManager marking
running jobs dead on GCS restart; VERDICT r3 ask #8)."""

import os
import subprocess
import sys
import time

import pytest

from conftest import kill_and_sweep
from ray_tpu._private.launch import spawn_head

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_client(address, authkey_hex, body, timeout=120):
    env = dict(os.environ)
    env["RAY_TPU_AUTHKEY_HEX"] = authkey_hex
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    script = (
        f"import sys; sys.path.insert(0, {REPO!r})\n"
        f"import ray_tpu\n"
        f"ray_tpu.init(address={address!r})\n"
    ) + body
    r = subprocess.run(
        [sys.executable, "-c", script],
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    assert r.returncode == 0, f"client failed:\n{r.stdout}\n{r.stderr}"
    return r.stdout + r.stderr


def _read_line_until(proc, prefix: str, timeout: float) -> str:
    """Read the child's stdout until a line with `prefix` appears; select()
    keeps the deadline real (a bare readline() would block forever if the
    child wedges before printing — exactly what chaos tests provoke)."""
    import select

    deadline = time.time() + timeout
    buf = ""
    while time.time() < deadline:
        r, _, _ = select.select([proc.stdout], [], [], 0.5)
        if not r:
            if proc.poll() is not None:
                raise AssertionError("phase-1 client died early")
            continue
        line = proc.stdout.readline()
        if not line:
            if proc.poll() is not None:
                raise AssertionError("phase-1 client died early")
            continue
        buf += line
        if line.startswith(prefix):
            return line.strip()
    raise AssertionError(f"client never printed {prefix!r}; output so far:\n{buf}")


def _wait_for_journal(
    persist: str, actor_name: str, job_id: str = None, timeout: float = 120.0
) -> None:
    """Poll the GCS journal until it holds THE named-actor record (not just
    any record — the job supervisor is also a persisted actor) and the
    RUNNING job status: the chaos kill must observe a captured state."""
    from ray_tpu._private import serialization
    from ray_tpu._private.gcs import GCS

    deadline = time.time() + timeout
    while time.time() < deadline:
        g = GCS()
        try:
            if g.load_from(persist):
                names = set()
                for blob in g.detached_actors.values():
                    try:
                        names.add(serialization.loads(blob).get("name"))
                    except Exception:
                        pass
                job_ok = (
                    job_id is None
                    or g.kv_get(f"job::{job_id}::status".encode()) == b"RUNNING"
                )
                if actor_name in names and job_ok:
                    return
        except Exception:
            pass  # torn read of a mid-write journal; retry
        time.sleep(0.2)
    raise AssertionError("journal never captured actor + running job")


def test_head_restart_mid_job_and_named_actor(tmp_path):
    """The VERDICT done-criterion in one chaos pass: kill the head while a
    job is mid-flight and a named OWNED actor exists; after restart with the
    same journal, the job is queryable as FAILED with a message and the
    named actor is reachable again (fresh state, replayed creation)."""
    persist = str(tmp_path / "gcs.bin")
    proc, info = spawn_head(
        num_cpus=4, num_tpus=0, timeout_s=60,
        extra_args=("--persist", persist, "--persist-interval", "0.2"),
    )
    client_proc = None
    try:
        # The phase-1 client must STAY ALIVE until the head dies: an owned
        # actor is killed (and its journal record dropped) the moment its
        # owner driver disconnects — the scenario is "head dies under a live
        # driver", not "driver leaves, then head dies".
        env = dict(os.environ)
        env["RAY_TPU_AUTHKEY_HEX"] = info["authkey_hex"]
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        script = f"""import sys; sys.path.insert(0, {REPO!r})
import time
import ray_tpu
ray_tpu.init(address={info["address"]!r})
from ray_tpu.job_submission import JobSubmissionClient

@ray_tpu.remote
class Counter:
    def __init__(self, start):
        self.n = start
    def value(self):
        return self.n

c = Counter.options(name="counter").remote(41)
assert ray_tpu.get(c.value.remote()) == 41

client = JobSubmissionClient()
job_id = client.submit_job(entrypoint="python -c 'import time; time.sleep(600)'")
for _ in range(240):
    if client.get_job_status(job_id) == "RUNNING":
        break
    time.sleep(0.5)
assert client.get_job_status(job_id) == "RUNNING"
print("JOBID=" + job_id, flush=True)
time.sleep(600)  # hold the actor's ownership until the parent kills us
"""
        client_proc = subprocess.Popen(
            [sys.executable, "-c", script],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        job_id = _read_line_until(client_proc, "JOBID=", timeout=180).split("=", 1)[1]
        # Don't fire the kill until a persist tick has actually journaled the
        # actor + running job.
        _wait_for_journal(persist, "counter", job_id=job_id)
    finally:
        kill_and_sweep(proc)  # hard kill mid-job (chaos, not graceful shutdown)
        if client_proc is not None:
            client_proc.kill()
            client_proc.wait(timeout=10)

    proc2, info2 = spawn_head(
        num_cpus=4, num_tpus=0, timeout_s=60,
        extra_args=("--persist", persist),
    )
    try:
        out2 = _run_client(info2["address"], info2["authkey_hex"], f"""
import ray_tpu
from ray_tpu.job_submission import JobSubmissionClient

client = JobSubmissionClient()
# Job state survived and was cleanly failed with a record.
info = client.get_job_info({job_id!r})
print("STATUS=" + info["status"])
print("MESSAGE=" + info.get("message", ""))

# The named owned actor is reachable again (creation replayed -> fresh
# state from the same creation args).
h = ray_tpu.get_actor("counter")
print("VALUE=" + str(ray_tpu.get(h.value.remote())))
""")
        assert "STATUS=FAILED" in out2
        assert "in flight when the head restarted" in out2
        assert "VALUE=41" in out2
    finally:
        proc2.terminate()
        proc2.wait(timeout=10)


def test_restored_owned_actor_is_killable_and_record_dropped(tmp_path):
    """A restored owned actor behaves like a named ownerless actor: kill
    removes it and its persisted record (no resurrection on a second
    restart)."""
    persist = str(tmp_path / "gcs.bin")
    proc, info = spawn_head(
        num_cpus=2, num_tpus=0, timeout_s=60,
        extra_args=("--persist", persist, "--persist-interval", "0.2"),
    )
    client_proc = None
    try:
        # Keep the owner ALIVE while the head dies (an exiting owner kills
        # the owned actor and drops its journal record first).
        env = dict(os.environ)
        env["RAY_TPU_AUTHKEY_HEX"] = info["authkey_hex"]
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        script = f"""import sys; sys.path.insert(0, {REPO!r})
import time
import ray_tpu
ray_tpu.init(address={info["address"]!r})
@ray_tpu.remote
class A:
    def ping(self):
        return "pong"
a = A.options(name="mortal").remote()
assert ray_tpu.get(a.ping.remote()) == "pong"
print("READY", flush=True)
time.sleep(600)
"""
        client_proc = subprocess.Popen(
            [sys.executable, "-c", script],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        _read_line_until(client_proc, "READY", timeout=120)
        # Wait for a persist tick to journal the record.
        _wait_for_journal(persist, "mortal")
    finally:
        kill_and_sweep(proc)
        if client_proc is not None:
            client_proc.kill()
            client_proc.wait(timeout=10)

    proc2, info2 = spawn_head(
        num_cpus=2, num_tpus=0, timeout_s=60,
        extra_args=("--persist", persist, "--persist-interval", "0.2"),
    )
    try:
        _run_client(info2["address"], info2["authkey_hex"], """
import time
import ray_tpu
h = ray_tpu.get_actor("mortal")
assert ray_tpu.get(h.ping.remote()) == "pong"
ray_tpu.kill(h)
for _ in range(40):
    try:
        ray_tpu.get_actor("mortal")
        time.sleep(0.25)
    except ValueError:
        print("killed ok")
        break
time.sleep(1.0)  # persist tick records the removal
""")
    finally:
        proc2.terminate()
        proc2.wait(timeout=10)

    proc3, info3 = spawn_head(
        num_cpus=2, num_tpus=0, timeout_s=60,
        extra_args=("--persist", persist),
    )
    try:
        out = _run_client(info3["address"], info3["authkey_hex"], """
import ray_tpu
try:
    ray_tpu.get_actor("mortal")
    print("RESURRECTED")
except ValueError:
    print("STAYS DEAD")
""")
        assert "STAYS DEAD" in out
    finally:
        proc3.terminate()
        proc3.wait(timeout=10)


def test_daemon_rejoins_restarted_head(tmp_path):
    """VERDICT r4 ask #8 (shrink head-death blast radius): SIGKILL the head
    under a live node daemon, restart it on the same address with the same
    journal — the daemon REJOINS without being respawned (same pid), and a
    task submitted afterward runs to completion on that node."""
    import socket

    from ray_tpu._private.launch import spawn_node_daemon

    persist = str(tmp_path / "gcs.bin")
    key = os.urandom(16).hex()
    # A fixed port so the restarted head binds the address the daemon retries.
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()

    old_env = os.environ.get("RAY_TPU_AUTHKEY_HEX")
    os.environ["RAY_TPU_AUTHKEY_HEX"] = key
    head = daemon = None
    try:
        head, info = spawn_head(
            port=port, num_cpus=0, num_tpus=0, timeout_s=60,
            extra_args=("--persist", persist, "--persist-interval", "0.2"),
        )
        daemon, _node_id = spawn_node_daemon(
            info["address"], shm_dir=str(tmp_path / "shm"),
            resources={"CPU": 2}, authkey_hex=key,
        )
        body = (
            "import ray_tpu\n"
            "@ray_tpu.remote\n"
            "def probe():\n"
            "    import os\n"
            "    return os.getpid()\n"
            "print('PID', ray_tpu.get(probe.remote(), timeout=60))\n"
        )
        out = _run_client(info["address"], key, body)
        assert "PID" in out

        # Chaos: SIGKILL the head; the daemon must survive and retry.
        kill_and_sweep(head)
        time.sleep(1.0)
        assert daemon.poll() is None, "daemon died with the head"

        head, info2 = spawn_head(
            port=port, num_cpus=0, num_tpus=0, timeout_s=60,
            extra_args=("--persist", persist, "--persist-interval", "0.2"),
        )
        assert info2["address"] == info["address"]

        # The daemon (same pid, never respawned) rejoins; once its node is
        # registered, a CPU task completes on it.
        deadline = time.time() + 90
        joined = False
        while time.time() < deadline:
            out = _run_client(
                info2["address"], key,
                "import ray_tpu\n"
                "ns = [n for n in ray_tpu.nodes() if n.get('alive')]\n"
                "print('CPUS', sum(n['resources'].get('CPU', 0) for n in ns))\n",
            )
            if "CPUS 2" in out:
                joined = True
                break
            time.sleep(1.0)
        assert joined, "daemon never rejoined the restarted head"
        assert daemon.poll() is None

        out = _run_client(info2["address"], key, body, timeout=120)
        assert "PID" in out, out
    finally:
        if old_env is None:
            os.environ.pop("RAY_TPU_AUTHKEY_HEX", None)
        else:
            os.environ["RAY_TPU_AUTHKEY_HEX"] = old_env
        for proc in (daemon, head):
            if proc is not None:
                try:
                    kill_and_sweep(proc)
                except Exception:
                    pass
