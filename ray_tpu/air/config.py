"""Run-level config dataclasses shared by Train and Tune.

Reference: `python/ray/air/config.py` (`ScalingConfig`, `RunConfig`,
`FailureConfig:512`, `CheckpointConfig`).

TPU-first delta: `ScalingConfig` carries an optional `mesh` (a
`ray_tpu.parallel.MeshSpec` or axis dict) describing the per-worker SPMD
layout — the ScalingConfig -> jax.sharding.Mesh seam of SURVEY.md §7 step 5.
`num_workers` remains the number of *processes* (one per TPU host);
`mesh` describes how each step shards over the global device set.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Union


@dataclass
class ScalingConfig:
    """How to scale training: worker gang size, resources, and mesh layout."""

    num_workers: int = 1
    # Each worker is granted `tpus_per_worker` chips (default ONE, whatever
    # the host holds) and can open those chips and no others: the scheduler
    # assigns chip indices and the worker's libtpu environment is confined to
    # them. A one-worker job on a four-chip host therefore sees one device;
    # ask for the host with tpus_per_worker=4, or for four one-chip workers
    # joined into one mesh with num_workers=4.
    use_tpu: bool = False
    resources_per_worker: Optional[Dict[str, float]] = None
    placement_strategy: str = "PACK"
    # TPU-native: SPMD mesh layout for the training step. Either a MeshSpec or
    # a dict of axis sizes, e.g. {"data": 8} or {"data": 2, "tensor": 4}.
    mesh: Optional[Union[Dict[str, int], Any]] = None
    # Chips each worker process owns: 1 (default), 2, 4 or 8 — the blocks
    # libtpu can present to one process (TPU hosts have 4 or 8 chips).
    tpus_per_worker: Optional[int] = None
    # Elastic gang membership (ISSUE 19): on a worker/node loss the gang
    # drains survivors at a step boundary and re-forms at the new world size
    # instead of failing the run (resizes do NOT consume FailureConfig's
    # max_failures budget), then re-expands toward num_workers when capacity
    # returns. Elastic gangs are scheduled by plain resources, not an
    # all-or-nothing placement group.
    elastic: bool = False
    # Floor below which a resize is impossible and the loss is treated as an
    # ordinary gang failure. Defaults to 1.
    min_workers: Optional[int] = None

    def __post_init__(self):
        if self.num_workers < 1:
            raise ValueError("num_workers must be >= 1")
        if self.min_workers is not None and not (
            1 <= self.min_workers <= self.num_workers
        ):
            raise ValueError("min_workers must be in [1, num_workers]")

    @property
    def _resources(self) -> Dict[str, float]:
        res = dict(self.resources_per_worker or {})
        if self.use_tpu and "TPU" not in res:
            res["TPU"] = float(self.tpus_per_worker or 1.0)
        if not self.use_tpu:
            res.pop("TPU", None)
        res.setdefault("CPU", 1.0)
        return res

    def as_placement_group_bundles(self) -> list:
        return [dict(self._resources) for _ in range(self.num_workers)]

    def mesh_spec(self):
        """Resolve the mesh layout (defaults to pure DP over all workers)."""
        from ray_tpu.parallel import MeshSpec

        if self.mesh is None:
            return None  # trainer defaults to DP over the devices it sees
        if isinstance(self.mesh, MeshSpec):
            return self.mesh
        return MeshSpec.from_dict(self.mesh)


@dataclass
class FailureConfig:
    """Retry policy for a run (reference: `air/config.py:512`).

    max_failures: total restarts-from-last-checkpoint allowed; 0 disables,
    -1 is unlimited.
    """

    max_failures: int = 0


@dataclass
class CheckpointConfig:
    """Checkpoint retention policy (reference `air/config.py` CheckpointConfig)."""

    num_to_keep: Optional[int] = None
    checkpoint_score_attribute: Optional[str] = None
    checkpoint_score_order: str = "max"
    checkpoint_frequency: int = 0
    checkpoint_at_end: bool = False

    def __post_init__(self):
        if self.num_to_keep is not None and self.num_to_keep <= 0:
            raise ValueError("num_to_keep must be positive or None")
        if self.checkpoint_score_order not in ("max", "min"):
            raise ValueError("checkpoint_score_order must be 'max' or 'min'")


@dataclass
class RunConfig:
    """Experiment-level settings: name, storage, failure + checkpoint policy."""

    name: Optional[str] = None
    storage_path: Optional[str] = None
    failure_config: FailureConfig = field(default_factory=FailureConfig)
    checkpoint_config: CheckpointConfig = field(default_factory=CheckpointConfig)
    # Metric-threshold dict, a `ray_tpu.tune.Stopper`, or a
    # `(trial_id, result) -> bool` callable.
    stop: Optional[Any] = None
    verbose: int = 1
    log_to_file: bool = False
    # Tune experiment-lifecycle hooks (`ray_tpu.tune.Callback` instances).
    callbacks: Optional[List[Any]] = None
