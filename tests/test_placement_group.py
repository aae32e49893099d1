"""Placement group tests (reference: `python/ray/tests/test_placement_group.py`)."""

import pytest

import ray_tpu
from ray_tpu.util import (
    PlacementGroupSchedulingStrategy,
    placement_group,
    remove_placement_group,
    tpu_slice_placement_group,
)


def test_pack_pg_basic(ray_start_regular):
    pg = placement_group([{"CPU": 1}, {"CPU": 1}], strategy="PACK")
    assert pg.ready(timeout=10)

    @ray_tpu.remote(num_cpus=1)
    def f():
        return "in-pg"

    strategy = PlacementGroupSchedulingStrategy(pg)
    assert ray_tpu.get(f.options(scheduling_strategy=strategy).remote(), timeout=30) == "in-pg"
    remove_placement_group(pg)


def test_strict_spread_needs_enough_nodes(ray_start_cluster):
    cluster = ray_start_cluster
    pg = placement_group([{"CPU": 1}, {"CPU": 1}], strategy="STRICT_SPREAD")
    assert not pg.ready(timeout=0.5)  # only one node so far
    cluster.add_node(num_cpus=1)
    assert pg.ready(timeout=10)


def test_strict_pack_infeasible(ray_start_cluster):
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=1)
    # 3 CPUs in one bundle-set cannot pack onto 1-CPU nodes.
    pg = placement_group([{"CPU": 3}], strategy="STRICT_PACK")
    assert not pg.ready(timeout=0.5)


def test_pg_bundle_index_and_capacity(ray_start_regular):
    pg = placement_group([{"CPU": 2}, {"CPU": 2}], strategy="PACK")
    assert pg.ready(timeout=10)

    @ray_tpu.remote(num_cpus=2)
    def f(i):
        return i

    strategy0 = PlacementGroupSchedulingStrategy(pg, placement_group_bundle_index=0)
    strategy1 = PlacementGroupSchedulingStrategy(pg, placement_group_bundle_index=1)
    vals = ray_tpu.get(
        [
            f.options(scheduling_strategy=strategy0).remote(0),
            f.options(scheduling_strategy=strategy1).remote(1),
        ],
        timeout=30,
    )
    assert vals == [0, 1]


def test_actor_in_pg(ray_start_regular):
    pg = placement_group([{"CPU": 1}], strategy="PACK")
    assert pg.ready(timeout=10)

    @ray_tpu.remote(num_cpus=1)
    class A:
        def ping(self):
            return "pong"

    a = A.options(scheduling_strategy=PlacementGroupSchedulingStrategy(pg)).remote()
    assert ray_tpu.get(a.ping.remote(), timeout=30) == "pong"


def test_remove_pg_releases_resources(ray_start_regular):
    pg = placement_group([{"CPU": 4}], strategy="PACK")
    assert pg.ready(timeout=10)
    avail = ray_tpu.available_resources()
    assert avail.get("CPU", 0) == 0
    remove_placement_group(pg)
    avail = ray_tpu.available_resources()
    assert avail.get("CPU", 0) == 4


def test_tpu_slice_pg_on_fake_hosts(ray_start_cluster):
    """Gang-reserve a fake 2-host TPU slice (the TPU analogue of the reference's
    FakeMultiNodeProvider testing trick)."""
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=2, num_tpus=4)
    cluster.add_node(num_cpus=2, num_tpus=4)
    pg = tpu_slice_placement_group(num_hosts=2, chips_per_host=4, cpus_per_host=1)
    assert pg.ready(timeout=10)

    # Chips are held by actors (one process owns a chip for its lifetime);
    # each host worker is granted all four chips of its host.
    @ray_tpu.remote(num_cpus=1, num_tpus=4)
    class HostWorker:
        def chips(self):
            import os

            return os.environ["TPU_VISIBLE_CHIPS"]

    strategy = PlacementGroupSchedulingStrategy(pg)
    hosts = [HostWorker.options(scheduling_strategy=strategy).remote() for _ in range(2)]
    assert ray_tpu.get([h.chips.remote() for h in hosts], timeout=30) == ["0,1,2,3"] * 2

    @ray_tpu.remote(num_tpus=4)
    def host_task():
        return 0

    with pytest.raises(ValueError, match="plain task cannot hold TPU chips"):
        host_task.remote()


def test_invalid_bundles_rejected(ray_start_regular):
    with pytest.raises(ValueError):
        placement_group([], strategy="PACK")
    with pytest.raises(ValueError):
        placement_group([{"CPU": 1}], strategy="NOT_A_STRATEGY")
