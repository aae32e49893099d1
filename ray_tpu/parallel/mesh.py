"""Device-mesh construction and sharding rules: the single vocabulary for
DP/FSDP/TP/PP/CP/EP across the framework.

The reference has no first-class parallelism beyond DP (SURVEY.md §2 inventory:
TP/PP/SP/EP all "NO"); its substrate is NCCL p2p. The TPU build instead makes the
mesh the core abstraction (the scaling-book recipe: pick a mesh, annotate
shardings, let XLA insert collectives):

 - `MeshSpec(data=, fsdp=, tensor=, pipeline=, context=, expert=)` names the six
   axes. Device order puts `tensor` innermost so tensor-parallel collectives ride
   the fastest ICI links, then context, expert, fsdp, pipeline, data outermost
   (data-parallel gradient reduction tolerates DCN).
 - `ShardingRules` maps *logical* array axes ("batch", "embed", "heads", ...) to
   mesh axes, so models annotate semantics and the trainer decides placement —
   the ScalingConfig -> mesh seam Train uses (SURVEY.md §7 step 5).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ray_tpu.util.tracing import annotate

AXIS_ORDER = ("data", "fsdp", "pipeline", "expert", "context", "tensor")


@dataclass(frozen=True)
class MeshSpec:
    data: int = 1
    fsdp: int = 1
    tensor: int = 1
    pipeline: int = 1
    context: int = 1
    expert: int = 1

    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(getattr(self, _FIELD_FOR_AXIS[a]) for a in AXIS_ORDER)

    @property
    def num_devices(self) -> int:
        n = 1
        for d in self.shape:
            n *= d
        return n

    def build(self, devices: Optional[Sequence] = None):
        """Build a jax.sharding.Mesh over `devices` (default: all devices)."""
        import jax
        from jax.sharding import Mesh

        devs = list(devices) if devices is not None else jax.devices()
        if len(devs) != self.num_devices:
            raise ValueError(
                f"MeshSpec wants {self.num_devices} devices "
                f"({dict(zip(AXIS_ORDER, self.shape))}), got {len(devs)}"
            )
        grid = np.array(devs).reshape(self.shape)
        return Mesh(grid, AXIS_ORDER)

    @classmethod
    def for_data_parallel(cls, num_devices: int) -> "MeshSpec":
        return cls(data=num_devices)

    @classmethod
    def from_dict(cls, d: Dict[str, int]) -> "MeshSpec":
        return cls(**{k: int(v) for k, v in d.items()})

    def replace(self, **kw) -> "MeshSpec":
        import dataclasses

        return dataclasses.replace(self, **kw)


_FIELD_FOR_AXIS = {
    "data": "data",
    "fsdp": "fsdp",
    "pipeline": "pipeline",
    "expert": "expert",
    "context": "context",
    "tensor": "tensor",
}


# --------------------------------------------------------------------------- logical sharding rules
Rule = Tuple[str, Optional[Tuple[str, ...]]]


@dataclass
class ShardingRules:
    """Logical-axis -> mesh-axis mapping, applied to model annotations.

    The default rules implement the standard transformer recipe:
      batch over (data, fsdp); embed over fsdp (ZeRO-3 style parameter shard);
      mlp/heads over tensor (megatron style); sequence over context (ring/
      all-to-all attention); experts over expert.
    """

    rules: Tuple[Rule, ...] = (
        ("batch", ("data", "fsdp")),
        ("sequence", ("context",)),
        ("embed", ("fsdp",)),
        ("mlp", ("tensor",)),
        ("heads", ("tensor",)),
        ("kv_heads", ("tensor",)),
        ("vocab", ("tensor",)),
        ("expert", ("expert",)),
        # Layer stacks shard over the pipeline axis (each stage group stores
        # n_layer/pipeline layers); on pipeline=1 meshes the axis is dropped by
        # the divisibility filter and layers stay replicated.
        ("layers", ("pipeline",)),
        ("stage", ("pipeline",)),
        ("head_dim", None),
        ("norm", None),
    )

    def mesh_axes(
        self,
        logical_axes: Sequence[Optional[str]],
        mesh=None,
        shape: Optional[Sequence[int]] = None,
    ):
        """PartitionSpec for an array annotated with logical axis names.

        With `mesh` + `shape`, mesh axes that don't divide the dimension are
        dropped (e.g. 2 heads on a tensor=4 mesh stay replicated) — models keep
        one annotation set across every mesh size.
        """
        from jax.sharding import PartitionSpec

        lookup = dict(self.rules)
        out: List = []
        used: set = set()
        for i, ax in enumerate(logical_axes):
            if ax is None:
                out.append(None)
                continue
            if ax not in lookup:
                raise ValueError(f"no sharding rule for logical axis '{ax}'")
            mesh_axes = lookup[ax]
            if mesh_axes is None:
                out.append(None)
                continue
            # An axis already consumed by another dimension cannot repeat.
            free = [a for a in mesh_axes if a not in used]
            if mesh is not None and shape is not None:
                # Pick the order-preserving subset of axes with the largest
                # total size that divides the dimension (a greedy prefix would
                # e.g. keep data=2 and then have to drop fsdp=8 on a dim of 8,
                # silently losing 4x parallelism).
                import itertools

                dim = shape[i]
                candidates = [a for a in free if mesh.shape[a] > 1]
                best: List[str] = []
                best_prod = 1
                # Exhaustive over subsets (rules map to <=3 axes, so <=8): a
                # larger subset is not necessarily a larger product.
                for r in range(len(candidates), 0, -1):
                    for combo in itertools.combinations(candidates, r):
                        prod = 1
                        for a in combo:
                            prod *= mesh.shape[a]
                        if dim % prod == 0 and prod > best_prod:
                            best, best_prod = list(combo), prod
                free = best
            used.update(free)
            if not free:
                out.append(None)
            elif len(free) == 1:
                out.append(free[0])
            else:
                out.append(tuple(free))
        return PartitionSpec(*out)

    def sharding(self, mesh, logical_axes: Sequence[Optional[str]], shape=None):
        from jax.sharding import NamedSharding

        return NamedSharding(mesh, self.mesh_axes(logical_axes, mesh=mesh, shape=shape))


def batch_spec():
    from jax.sharding import PartitionSpec

    return PartitionSpec(("data", "fsdp"), "context")


def batch_sharding(mesh):
    from jax.sharding import NamedSharding

    return NamedSharding(mesh, batch_spec())


def replicated(mesh):
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec())


# --------------------------------------------------------------------------- host<->global helpers
def host_local_to_global(mesh, spec, array):
    """Per-host shard -> global jax.Array (multi-controller boundary helper)."""
    import jax
    from jax.sharding import NamedSharding

    with annotate("ray_tpu.parallel.host_local_to_global", bytes=getattr(array, "nbytes", 0)):
        return jax.make_array_from_process_local_data(NamedSharding(mesh, spec), array)


def global_to_host_local(garr) -> np.ndarray:
    """This host's shards of a global array, concatenated (inverse of above for
    fully-addressable layouts)."""
    shards = sorted(garr.addressable_shards, key=lambda s: s.index)
    return np.concatenate([np.asarray(s.data) for s in shards], axis=0) if shards else np.asarray(garr)


def shard_params(params, mesh, rules: ShardingRules, logical_axes):
    """device_put a pytree of host params according to per-leaf logical axes."""
    import jax

    return jax.tree.map(
        lambda p, ax: jax.device_put(p, rules.sharding(mesh, ax, shape=p.shape)),
        params,
        logical_axes,
    )
