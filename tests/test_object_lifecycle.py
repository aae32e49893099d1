"""Object lifecycle: ownership refcounting, capacity enforcement, and lineage
reconstruction.

The reference covers this surface with `reference_count_test.cc`,
plasma eviction tests, and `test_reconstruction.py`; the mechanisms here are
 - owner refcounting: `/root/reference/src/ray/core_worker/reference_count.h:59`
 - capacity/eviction: `object_manager/plasma/eviction_policy.h`
 - lineage re-execution: `core_worker/object_recovery_manager.h:41`.
"""

import gc
import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu._private.worker import flush_ref_ops, global_worker


@pytest.fixture
def ray_start_regular():
    """File-segment mode: these tests assert on per-object segment files
    (the native-arena store has its own suite, test_native_arena.py)."""
    ctx = ray_tpu.init(
        num_cpus=4, _system_config={"use_native_object_arena": False}
    )
    yield ctx
    ray_tpu.shutdown()


@pytest.fixture
def small_store():
    """Runtime with a 40MB object store cap (file-segment mode)."""
    ctx = ray_tpu.init(
        num_cpus=2,
        _system_config={
            "object_store_memory": 40 * 1024 * 1024,
            "use_native_object_arena": False,
        },
    )
    yield ctx
    ray_tpu.shutdown()


def _segment_path(ref):
    return os.path.join(global_worker.store.shm_dir, ref.hex())


def _wait_gone(path, timeout=5.0):
    deadline = time.time() + timeout
    while os.path.exists(path) and time.time() < deadline:
        time.sleep(0.05)
    return not os.path.exists(path)


def test_dropping_ref_frees_segment(ray_start_regular):
    ref = ray_tpu.put(np.arange(500_000))
    seg = _segment_path(ref)
    assert os.path.exists(seg)
    del ref
    gc.collect()
    flush_ref_ops()
    assert _wait_gone(seg), "segment should be unlinked once the last ref drops"


def test_task_dependency_pins_object(ray_start_regular):
    big = ray_tpu.put(np.arange(400_000))

    @ray_tpu.remote
    def slow_sum(x):
        time.sleep(0.3)
        return int(x.sum())

    fut = slow_sum.remote(big)
    del big  # dropped before the task runs; the dep pin must keep it alive
    gc.collect()
    flush_ref_ops()
    assert ray_tpu.get(fut, timeout=30) == int(np.arange(400_000).sum())


def test_contained_ref_pinned_by_container(ray_start_regular):
    inner = ray_tpu.put(np.arange(300_000))
    inner_seg = _segment_path(inner)
    outer = ray_tpu.put({"k": [inner]})
    del inner
    gc.collect()
    flush_ref_ops()
    time.sleep(0.3)
    assert os.path.exists(inner_seg), "container must pin nested refs"

    @ray_tpu.remote
    def read_inner(d):
        return int(ray_tpu.get(d["k"][0]).sum())

    assert ray_tpu.get(read_inner.remote(outer), timeout=30) == int(
        np.arange(300_000).sum()
    )
    del outer
    gc.collect()
    flush_ref_ops()
    assert _wait_gone(inner_seg), "nested object should free with its container"


def test_worker_borrowed_ref_outlives_driver_ref(ray_start_regular):
    @ray_tpu.remote
    class Holder:
        def __init__(self):
            self.ref = None

        def hold(self, refs):
            self.ref = refs[0]

        def read(self):
            return int(ray_tpu.get(self.ref).sum())

    h = Holder.remote()
    big = ray_tpu.put(np.arange(350_000))
    ray_tpu.get(h.hold.remote([big]))
    del big
    gc.collect()
    flush_ref_ops()
    time.sleep(0.3)
    # The actor's borrow keeps it alive even though the driver dropped its ref.
    assert ray_tpu.get(h.read.remote(), timeout=30) == int(np.arange(350_000).sum())


def test_put_loop_stays_under_capacity(small_store):
    shm = global_worker.store.shm_dir
    # 16 x 8MB through a 40MB cap: release-per-iteration must reclaim (3x
    # the cap total — enough to prove eviction without paying 30 full GC
    # passes of tier-1 wall-clock).
    for _ in range(16):
        ref = ray_tpu.put(np.zeros(1_000_000))  # 8MB each
        del ref
        gc.collect()
    def size(name):
        try:
            return os.path.getsize(os.path.join(shm, name))
        except FileNotFoundError:  # reclaimed between the listing and here: the runtime unlinks on its own thread
            return 0

    usage = sum(size(f) for f in os.listdir(shm))
    assert usage <= 40 * 1024 * 1024


def test_put_raises_when_full_and_recovers():
    """With spilling disabled, over-capacity puts raise (the old hard cap)."""
    ray_tpu.init(
        num_cpus=2,
        _system_config={
            "object_store_memory": 40 * 1024 * 1024,
            "use_native_object_arena": False,
            "object_spilling": False,
        },
    )
    try:
        held = []
        with pytest.raises(ray_tpu.exceptions.ObjectStoreFullError):
            for _ in range(10):
                held.append(ray_tpu.put(np.zeros(1_000_000)))
        held.clear()
        gc.collect()
        flush_ref_ops()
        ray_tpu.put(np.zeros(1_000_000))  # fits again after frees
    finally:
        ray_tpu.shutdown()


def _spill_dir_for_session():
    import tempfile

    return os.path.join(
        tempfile.gettempdir(),
        os.path.basename(global_worker.session_dir.rstrip("/")) + "_spill",
    )


@pytest.mark.parametrize("arena", [False, True], ids=["files", "arena"])
def test_spilling_over_capacity_with_live_refs(arena):
    """Puts beyond object_store_memory relocate to the disk spill dir instead
    of raising (plasma's fallback-allocation analogue): every value stays
    readable, shm stays under the cap, and dropping refs deletes spill files."""
    ray_tpu.init(
        num_cpus=2,
        _system_config={
            "object_store_memory": 40 * 1024 * 1024,
            "use_native_object_arena": arena,
            "object_arena_bytes": 40 * 1024 * 1024,
        },
    )
    try:
        held = [ray_tpu.put(np.full(1_000_000, i)) for i in range(10)]  # 80MB
        spill_dir = _spill_dir_for_session()
        assert os.path.isdir(spill_dir) and len(os.listdir(spill_dir)) >= 4
        # Every object reads back correctly, spilled or not.
        for i, ref in enumerate(held):
            arr = ray_tpu.get(ref)
            assert arr[0] == i and arr.shape == (1_000_000,)
        del arr, ref  # the loop bindings still pin the last object
        # A worker task can consume a spilled object too. A dedicated object
        # carries this check: a task-arg ref is retained by the task record
        # for lineage reconstruction, so it (correctly) outlives our handle.
        extra = ray_tpu.put(np.full(1_000_000, 42.0))

        @ray_tpu.remote
        def total(x):
            return float(x.sum())

        assert ray_tpu.get(total.remote(extra)) == 42.0 * 1_000_000
        # Dropping the held refs deletes their spill files.
        held_hex = {r.hex() for r in held}
        held.clear()
        gc.collect()
        flush_ref_ops()
        deadline = time.time() + 10
        while (
            held_hex & set(os.listdir(spill_dir)) and time.time() < deadline
        ):
            time.sleep(0.05)
        assert not held_hex & set(os.listdir(spill_dir))
    finally:
        ray_tpu.shutdown()
    assert not os.path.exists(spill_dir)  # shutdown removes the spill dir


def test_reconstruction_after_segment_loss(ray_start_regular):
    @ray_tpu.remote
    def produce():
        from ray_tpu._private.worker import global_worker as gw

        ctx = gw.context
        n = ctx.kv("get", b"prod_runs")
        ctx.kv("put", b"prod_runs", str(int(n or 0) + 1).encode())
        return np.arange(250_000)

    ref = produce.remote()
    v1 = ray_tpu.get(ref, timeout=30)
    os.unlink(_segment_path(ref))
    global_worker.store._segments.clear()  # drop cached mmap so the loss is visible
    v2 = ray_tpu.get(ref, timeout=60)
    np.testing.assert_array_equal(v1, v2)
    assert int(global_worker.context.kv("get", b"prod_runs")) == 2  # re-executed


def test_put_objects_are_not_reconstructable(ray_start_regular):
    ref = ray_tpu.put(np.arange(200_000))
    os.unlink(_segment_path(ref))
    global_worker.store._segments.clear()
    with pytest.raises(ray_tpu.exceptions.ObjectLostError):
        ray_tpu.get(ref, timeout=30)


def test_actor_restart_keeps_creation_args_alive(ray_start_regular):
    """Creation args stay pinned for the actor's lifetime: restarting replays
    the creation task, and put() args have no lineage to rebuild from."""
    big = ray_tpu.put(np.arange(300_000))

    @ray_tpu.remote(max_restarts=1)
    class A:
        def __init__(self, x):
            self.total = int(x.sum())

        def total_(self):
            return self.total

        def crash(self):
            os._exit(1)

    a = A.remote(big)
    expect = int(np.arange(300_000).sum())
    assert ray_tpu.get(a.total_.remote(), timeout=30) == expect
    del big  # actor must survive losing the driver's ref
    gc.collect()
    flush_ref_ops()
    time.sleep(0.3)
    try:
        ray_tpu.get(a.crash.remote(), timeout=30)
    except ray_tpu.exceptions.RayActorError:
        pass
    # Restarted actor re-ran __init__(big): the arg was still alive.
    assert ray_tpu.get(a.total_.remote(), timeout=60) == expect


def test_lineage_gc_bounds_task_table(ray_start_regular):
    """Completed task records whose returns are fully freed are evicted, so
    the task table stays bounded on long-running drivers; records whose
    returns feed retained lineage survive until the chain is released."""
    from ray_tpu._private.worker import global_worker

    sched = global_worker.context.scheduler

    @ray_tpu.remote
    def make():
        return np.arange(1000)

    @ray_tpu.remote
    def consume(x):
        return int(x.sum())

    before = len(sched.tasks)
    for _ in range(50):
        r = ray_tpu.get(make.remote())
        del r
    gc.collect()
    flush_ref_ops()
    # One more round-trip so the scheduler processes the queued releases.
    ray_tpu.get(make.remote())
    gc.collect()
    flush_ref_ops()
    time.sleep(0.2)
    ray_tpu.get(make.remote())
    assert len(sched.tasks) - before < 20, len(sched.tasks) - before

    # Lineage chain: mid's record must survive while tail is alive.
    mid = make.remote()
    tail = consume.remote(mid)
    ray_tpu.get(tail)
    mid_task = mid.task_id
    del mid
    gc.collect()
    flush_ref_ops()
    ray_tpu.get(make.remote())  # nudge
    # tail is still referenced -> consume's record retained -> make's record
    # (its dep producer) retained even though our mid handle is gone.
    assert mid_task in sched.tasks
    del tail
    gc.collect()
    flush_ref_ops()
    deadline = time.time() + 5
    while mid_task in sched.tasks and time.time() < deadline:
        ray_tpu.get(make.remote())
        time.sleep(0.05)
    assert mid_task not in sched.tasks


def test_lineage_gc_after_actor_death(ray_start_regular):
    """Actor churn does not leak creation records: once the actor is DEAD,
    its creation record (and its constructor-arg lineage) is evicted."""
    from ray_tpu._private.worker import global_worker

    sched = global_worker.context.scheduler

    @ray_tpu.remote
    def produce():
        return np.arange(1000)

    @ray_tpu.remote
    class A:
        def __init__(self, x):
            self.n = int(x.sum())

        def get(self):
            return self.n

    before = len(sched.tasks)
    for _ in range(10):
        x = produce.remote()
        a = A.remote(x)
        assert ray_tpu.get(a.get.remote()) == 499500
        ray_tpu.kill(a)
        del x, a
    gc.collect()
    flush_ref_ops()
    deadline = time.time() + 5
    while len(sched.tasks) - before > 5 and time.time() < deadline:
        ray_tpu.get(produce.remote())  # nudge release processing
        time.sleep(0.05)
    assert len(sched.tasks) - before <= 5, len(sched.tasks) - before
