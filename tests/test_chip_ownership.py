"""One process for each chip, enforced by the scheduler — on a fake four-chip
node (`num_tpus=4`; no jax device is involved, the grant is environment) —
and what a process that runs jax on a chip does first.

The invariant: a process the scheduler granted k chips can open exactly those
k; a process granted none can open none.
"""

import os
import time

import pytest

import ray_tpu
from ray_tpu._private.accelerators import tpu as tpu_accel


@ray_tpu.remote(num_tpus=1)
class OneChip:
    def env(self):
        return {k: os.environ.get(k) for k in ("JAX_PLATFORMS",) + tpu_accel.CHIP_ENV_VARS}

    def chip(self):
        return int(os.environ["TPU_VISIBLE_CHIPS"])


@pytest.fixture
def four_chip_node(monkeypatch):
    # What a TPU VM exports: workers must not take their platform from it.
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.setenv("TPU_PROCESS_BOUNDS", "9,9,9")
    ctx = ray_tpu.init(num_cpus=8, num_tpus=4)
    yield ctx
    ray_tpu.shutdown()


def test_take_chips_grants_aligned_blocks_and_never_oversubscribes():
    free = [0, 1, 2, 3]
    assert tpu_accel.take_chips(free, 1) == (0,)
    assert tpu_accel.take_chips(free, 2) == (2, 3)  # [1, 2] is not a block
    assert tpu_accel.take_chips(free, 2) is None
    assert tpu_accel.take_chips(free, 1) == (1,)
    assert free == [] and tpu_accel.take_chips(free, 1) is None
    assert tpu_accel.chip_env(()) == {"JAX_PLATFORMS": "cpu"}
    assert tpu_accel.chip_env((2, 3)) == {
        "TPU_VISIBLE_CHIPS": "2,3",
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,2,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


def test_actors_get_disjoint_chips_a_fifth_waits_and_dead_actors_return_theirs(four_chip_node):
    actors = [OneChip.remote() for _ in range(4)]
    chips = ray_tpu.get([a.chip.remote() for a in actors], timeout=60)
    assert sorted(chips) == [0, 1, 2, 3]
    env = ray_tpu.get(actors[0].env.remote())
    assert env["JAX_PLATFORMS"] == "tpu,cpu"  # a granted worker keeps the host's platform
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"  # not the inherited 9,9,9

    fifth = OneChip.remote()
    ref = fifth.chip.remote()
    ready, _ = ray_tpu.wait([ref], timeout=1.5)
    assert ready == []  # refused, not oversubscribed

    ray_tpu.kill(actors[2])
    assert ray_tpu.get(ref, timeout=60) == chips[2]  # the dead actor's chip, reused


def test_worker_without_a_grant_is_pinned_off_the_chip(four_chip_node):
    @ray_tpu.remote
    def platform():
        return os.environ.get("JAX_PLATFORMS"), os.environ.get("TPU_PROCESS_BOUNDS")

    @ray_tpu.remote
    class NoChip:
        def platform(self):
            return os.environ.get("JAX_PLATFORMS"), os.environ.get("TPU_VISIBLE_CHIPS")

    assert ray_tpu.get(platform.remote(), timeout=60) == ("cpu", None)
    assert ray_tpu.get(NoChip.remote().platform.remote(), timeout=60) == ("cpu", None)


def test_tasks_and_fractions_cannot_hold_chips(four_chip_node):
    @ray_tpu.remote(num_tpus=1)
    def task():
        return 0

    with pytest.raises(ValueError, match="plain task cannot hold TPU chips"):
        task.remote()
    for bad in (0.5, 3):
        with pytest.raises(ValueError, match="1, 2, 4 or 8 whole chips"):
            OneChip.options(num_tpus=bad).remote()


def test_use_tpu_worker_on_cpu_fails_before_user_code():
    """The bundle holds a chip, jax comes up on CPU (here: the tests' own
    JAX_PLATFORMS=cpu; on a real host: libtpu could not open the chip, which
    jax only warns about) -> on_start raises, the loop never runs."""
    from ray_tpu.air import ScalingConfig
    from ray_tpu.train import TrainingFailedError
    from ray_tpu.train.jax import JaxTrainer

    def loop(config):
        raise AssertionError("user code ran on a device that was not asked for")

    ray_tpu.init(num_cpus=4, num_tpus=4)
    try:
        trainer = JaxTrainer(loop, scaling_config=ScalingConfig(num_workers=1, use_tpu=True))
        with pytest.raises(TrainingFailedError, match=r"granted 1 TPU chip\(s\).*'cpu' device"):
            trainer.fit()
    finally:
        ray_tpu.shutdown()


def test_one_chip_workers_sharing_a_host_are_told_how_to_form_one_topology():
    """4 x 1 on a four-chip host is the formation verified on the v5e (PR 21):
    each process is task <chip> of a 2x2 process grid on its own port. Anything
    else that shares a host is refused, not guessed."""
    from ray_tpu.train.jax.config import _tpu_process_envs

    def grant(host, *chips):
        return {"host": host, "chips": list(chips), "host_chips": 4}

    envs = _tpu_process_envs([grant("h", 2), grant("h", 0), grant("h", 3), grant("h", 1)])
    addresses = "localhost:8476,localhost:8477,localhost:8478,localhost:8479"
    assert envs[0] == {
        "TPU_PROCESS_BOUNDS": "2,2,1", "TPU_PROCESS_ADDRESSES": addresses,
        "TPU_PROCESS_PORT": "8478", "CLOUD_TPU_TASK_ID": "2",
    }
    assert [e["CLOUD_TPU_TASK_ID"] for e in envs] == ["2", "0", "3", "1"]
    # One worker per host (each owning it): libtpu's own metadata joins them.
    assert _tpu_process_envs([grant("a", 0, 1, 2, 3), grant("b", 0, 1, 2, 3)]) == [{}, {}]
    for unverified in ([grant("h", 0), grant("h", 1)], [grant("h", 0, 1), grant("h", 2, 3)]):
        with pytest.raises(RuntimeError, match="formation not supported"):
            _tpu_process_envs(unverified)


def test_chipless_cluster_names_detection():
    from ray_tpu.train._internal.backend_executor import _tpu_shortfall

    ray_tpu.init(num_cpus=2, num_tpus=0)
    try:
        msg = _tpu_shortfall([{"CPU": 1.0, "TPU": 1.0}])
    finally:
        ray_tpu.shutdown()
    assert "asks for 1 TPU chip(s) and the cluster has 0" in msg
    assert "/dev/vfio/" in msg and "RAY_TPU_NUM_CHIPS" in msg


def test_detection_trusts_device_files_over_the_vm_description(monkeypatch):
    """Both v5e machines this repo runs on export TPU_CHIPS_PER_HOST_BOUNDS=2,2,1;
    one of them can open one chip. /dev/vfio/<n> is what says so (PR 21)."""
    files = {"/dev/accel*": [], "/dev/vfio/[0-9]*": ["/dev/vfio/0"]}
    monkeypatch.setattr(tpu_accel.glob, "glob", lambda pattern: files[pattern])
    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
    monkeypatch.delenv("RAY_TPU_NUM_CHIPS", raising=False)
    assert tpu_accel.detect_num_tpu_chips() == 1
    files["/dev/vfio/[0-9]*"] = []
    assert tpu_accel.detect_num_tpu_chips() == 4  # metadata only when no device file
    # What a chip grant writes is not a description of the host.
    monkeypatch.delenv("TPU_CHIPS_PER_HOST_BOUNDS")
    monkeypatch.setenv("TPU_CHIPS_PER_PROCESS_BOUNDS", "2,2,1")
    monkeypatch.setenv("TPU_CHIPS", "4")
    assert tpu_accel.detect_num_tpu_chips() == 0
    monkeypatch.setenv("RAY_TPU_NUM_CHIPS", "8")
    assert tpu_accel.detect_num_tpu_chips() == 8


def test_compile_cache_is_placed_from_outside_or_at_one_fixed_path(monkeypatch, tmp_path):
    import jax

    from ray_tpu._private.accelerators import jax_process

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = jax.config.jax_compilation_cache_dir
    try:
        # Placed from outside: jax reads the variable itself, nothing is set in code.
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert jax_process.configure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        # Not placed: one path, a function of the checkout alone, git-ignored.
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert jax_process.configure_compile_cache() == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(repo, ".jax_cache")
        with open(os.path.join(repo, ".gitignore")) as fh:
            assert ".jax_cache/" in fh.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


class _SlowExit:
    """A worker process that stays alive `linger` seconds past its real exit:
    what a SIGKILLed holder of gigabytes of HBM looks like to the kernel."""

    def __init__(self, inner, linger):
        self._inner, self._linger, self._release_at = inner, linger, None
        self.pid = inner.pid

    def terminate(self):
        self._inner.terminate()

    def is_alive(self):
        if self._inner.is_alive():
            return True
        if self._release_at is None:
            self._release_at = time.time() + self._linger
        return time.time() < self._release_at

    def join(self, timeout=None):
        deadline = time.time() + (timeout or 0)
        while self.is_alive() and time.time() < deadline:
            time.sleep(0.02)


def test_shutdown_waits_for_chip_holding_workers():
    from ray_tpu._private.worker import global_worker

    ray_tpu.init(num_cpus=4, num_tpus=4)
    holder = OneChip.remote()
    ray_tpu.get(holder.chip.remote(), timeout=60)
    sched = global_worker.context.scheduler
    (wh,) = [w for n in sched.nodes.values() for w in n.workers.values() if w.tpu_chips]
    slow = wh.process = _SlowExit(wh.process, linger=3.0)
    ray_tpu.shutdown()
    assert not slow.is_alive()  # shutdown() returned only once the holder was gone
