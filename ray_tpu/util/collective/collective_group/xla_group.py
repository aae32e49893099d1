"""XLA collective group: the TPU-native replacement for the reference's
`NCCLGroup` (`python/ray/util/collective/collective_group/nccl_collective_group.py:127`).

Where NCCL offers eager per-call kernels on CUDA streams, ICI collectives exist
only *inside compiled XLA programs* (SURVEY.md §7 "hard parts"). So this group
traces and jits one shard_map program per (op, shape, dtype) and caches the
compiled executable — the first call pays compilation, subsequent calls are a
single dispatch onto the ICI mesh.

Group shapes:
 - world_size == 1: the group spans this process's local devices; use the
   `*_multidevice` entry points (analogue of the reference's `*_multigpu`) or
   hand in an already-sharded jax.Array.
 - world_size > 1 (one process per TPU host): rendezvous via the GCS KV
   publishes rank 0's coordinator address, every rank calls
   `jax.distributed.initialize`, and the group mesh is (processes, local
   devices); cross-process traffic rides ICI/DCN via XLA, exactly like a bare
   multi-controller JAX program.
"""

from __future__ import annotations

import functools
import socket
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ray_tpu.util.collective.collective_group.base_group import BaseGroup
from ray_tpu.util.collective.rendezvous import clear, publish, wait_for
from ray_tpu.util.collective.types import ReduceOp


def _psum_like(op: ReduceOp, axis: str):
    import jax

    if op == ReduceOp.SUM:
        return lambda x: jax.lax.psum(x, axis)
    if op == ReduceOp.MAX:
        return lambda x: jax.lax.pmax(x, axis)
    if op == ReduceOp.MIN:
        return lambda x: jax.lax.pmin(x, axis)
    if op == ReduceOp.MEAN:
        return lambda x: jax.lax.pmean(x, axis)
    if op == ReduceOp.PRODUCT:
        # exp(sum(log)) — valid for positive operands; sign handling would need
        # a second psum over sign bits, omitted as the reference backends share
        # this domain restriction.
        return lambda x: jax.numpy.exp(jax.lax.psum(jax.numpy.log(x), axis))
    raise ValueError(f"unsupported op {op} for XLA backend")


class XLAGroup(BaseGroup):
    def __init__(
        self,
        world_size: int,
        rank: int,
        group_name: str,
        kv=None,
        devices: Optional[List] = None,
    ):
        super().__init__(world_size, rank, group_name)
        import jax

        self._jax = jax
        self._kv = kv
        if world_size > 1:
            self._distributed_init(kv)
        self.devices = list(devices) if devices is not None else jax.devices()
        self.local_devices = [d for d in self.devices if d.process_index == jax.process_index()]
        ndev = len(self.devices)
        nlocal = max(1, len(self.local_devices))
        from jax.sharding import Mesh

        self.mesh = Mesh(
            np.array(self.devices).reshape(world_size, ndev // max(world_size, 1))
            if world_size > 1
            else np.array(self.devices).reshape(1, ndev),
            ("proc", "local"),
        )
        self._nlocal = nlocal
        self._cache: Dict[Tuple, Any] = {}

    def _distributed_init(self, kv):
        """KV-based rendezvous -> jax.distributed.initialize (the seam the
        reference fills with a named NCCLUniqueIDStore actor)."""
        import jax

        # Probe WITHOUT touching the backend: jax.process_count() would
        # initialize XLA and make distributed.initialize() impossible.
        if jax.distributed.is_initialized():
            if jax.process_count() != self.world_size:
                raise RuntimeError(
                    f"jax.distributed already initialized with "
                    f"{jax.process_count()} processes; group wants "
                    f"{self.world_size}"
                )
            return  # already initialized (e.g. by JaxBackend.on_start)
        key = f"collective/{self.group_name}/jax_coordinator".encode()
        if self.rank == 0:
            host = socket.gethostbyname(socket.gethostname())
            with socket.socket() as s:
                s.bind(("", 0))
                port = s.getsockname()[1]
            addr = f"{host}:{port}"
            publish(kv, key, addr.encode())
        else:
            addr = wait_for(kv, key).decode()
        jax.distributed.initialize(
            coordinator_address=addr,
            num_processes=self.world_size,
            process_id=self.rank,
        )

    # ------------------------------------------------------------------ compiled program cache
    def _compiled(self, kind: str, op: ReduceOp, shape, dtype, extra=()):
        key = (kind, op, tuple(shape), str(dtype), extra)
        fn = self._cache.get(key)
        if fn is None:
            fn = self._build(kind, op, extra)
            self._cache[key] = fn
        return fn

    def _build(self, kind: str, op: ReduceOp, extra):
        import jax
        from jax.sharding import PartitionSpec as P

        mesh = self.mesh
        axis = "proc" if self.world_size > 1 else "local"
        red = _psum_like(op, axis)

        if kind == "allreduce":
            body = red
            in_spec, out_spec = P(axis), P()
        elif kind == "allgather":
            body = lambda x: jax.lax.all_gather(x, axis, axis=0, tiled=True)
            in_spec, out_spec = P(axis), P()
        elif kind == "reducescatter":
            # Per-shard block is (1, *shape): drop the stack dim, then scatter
            # the contribution's own leading dim across ranks.
            body = lambda x: jax.lax.psum_scatter(x[0], axis, scatter_dimension=0, tiled=True)[None]
            in_spec, out_spec = P(axis), P(axis)
        elif kind == "broadcast":
            root = extra[0]

            def body(x):
                i = jax.lax.axis_index(axis)
                contrib = jax.numpy.where(i == root, 1.0, 0.0).astype(x.dtype)
                return jax.lax.psum(x * contrib, axis)

            in_spec, out_spec = P(axis), P()
        elif kind == "sendrecv":
            perm = list(extra)

            def body(x):
                return jax.lax.ppermute(x, axis, perm)

            in_spec, out_spec = P(axis), P(axis)
        else:
            raise ValueError(kind)

        smapped = jax.shard_map(
            body, mesh=mesh, in_specs=in_spec, out_specs=out_spec, check_vma=False
        )
        return jax.jit(smapped)

    # ------------------------------------------------------------------ data movement
    @staticmethod
    def _is_device_array(tensor) -> bool:
        import jax

        return isinstance(tensor, jax.Array)

    def _to_group_array(self, tensor, spec_axis="proc"):
        """Stack this process's contribution into a (world, *shape) global array
        sharded across processes (replicated over local devices). A
        device-resident `jax.Array` input stays on device — no host numpy
        staging (the D2H+H2D round trip the public API used to pay)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        local = tensor if self._is_device_array(tensor) else np.asarray(tensor)
        sharding = NamedSharding(self.mesh, P("proc"))
        if self.world_size > 1:
            return jax.make_array_from_process_local_data(sharding, local[None])
        return jax.device_put(local[None], NamedSharding(self.mesh, P()))

    @staticmethod
    def _from_group(result, want_device: bool):
        """Return the collective's result in the caller's currency: a
        device-resident jax.Array for jax.Array inputs, host numpy otherwise."""
        return result if want_device else np.asarray(result)

    def _shard_over_local(self, tensors: List):
        """Lay a list of per-device tensors out as one array sharded over the
        'local' mesh axis (the *_multidevice path)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        if len(tensors) != self._nlocal:
            raise ValueError(
                f"expected {self._nlocal} per-device tensors, got {len(tensors)}"
            )
        stacked = np.stack([np.asarray(t) for t in tensors])
        return jax.device_put(stacked, NamedSharding(self.mesh, P("local")))

    # ------------------------------------------------------------------ collectives (process-level)
    def allreduce(self, tensor, op: ReduceOp = ReduceOp.SUM):
        if self.world_size == 1:
            return tensor if self._is_device_array(tensor) else np.asarray(tensor)
        want_device = self._is_device_array(tensor)
        garr = self._to_group_array(tensor)
        fn = self._compiled("allreduce", op, garr.shape, garr.dtype)
        out = fn(garr)
        return self._from_group(out[0], want_device)

    def barrier(self):
        self.allreduce(np.zeros((1,), np.float32))

    def reduce(self, tensor, root_rank: int = 0, op: ReduceOp = ReduceOp.SUM):
        # Implemented as allreduce + root filter. On a bidirectional ring this
        # costs 2(N-1)/N x B per link vs (N-1)/N x B for a true reduce-to-root
        # tree — a 2x bound, not Nx; XLA exposes no reduce-to-root HLO and a
        # hand-rolled ppermute tree would serialize log(N) full-B hops, which
        # is slower on ICI for all realistic N. Revisit only if profiles show
        # reduce-heavy host loops (DP grad sync never takes this path — it is
        # fused into the jitted step).
        out = self.allreduce(tensor, op)
        return out if self.rank == root_rank else None

    def broadcast(self, tensor, root_rank: int = 0):
        # Masked psum (root contributes, others zero): same 2x-of-optimal ring
        # bound as reduce() above, same rationale for not hand-rolling a tree.
        if self.world_size == 1:
            return tensor if self._is_device_array(tensor) else np.asarray(tensor)
        want_device = self._is_device_array(tensor)
        garr = self._to_group_array(tensor)
        fn = self._compiled("broadcast", ReduceOp.SUM, garr.shape, garr.dtype, (root_rank,))
        return self._from_group(fn(garr)[0], want_device)

    def allgather(self, tensor):
        if self.world_size == 1:
            return [np.asarray(tensor)]
        garr = self._to_group_array(tensor)
        fn = self._compiled("allgather", ReduceOp.SUM, garr.shape, garr.dtype)
        out = np.asarray(fn(garr))
        return [out[i] for i in range(self.world_size)]

    def reducescatter(self, tensor, op: ReduceOp = ReduceOp.SUM):
        if self.world_size == 1:
            return np.asarray(tensor)
        garr = self._to_group_array(tensor)
        fn = self._compiled("reducescatter", op, garr.shape, garr.dtype)
        return np.asarray(fn(garr).addressable_shards[0].data)[0]

    def send(self, tensor, dst_rank: int):
        raise NotImplementedError(
            "XLA collectives are SPMD: eager one-sided send/recv has no ICI "
            "equivalent. Use sendrecv() (all ranks participate, lowered to "
            "ppermute) or the 'tcp' backend for eager host-data p2p."
        )

    def recv(self, shape, dtype, src_rank: int):
        raise NotImplementedError(
            "XLA collectives are SPMD: use sendrecv() or the 'tcp' backend."
        )

    def sendrecv(self, tensor, perm: List[Tuple[int, int]]):
        """All ranks enter; each receives from whoever permutes to it
        (lax.ppermute over the process axis)."""
        if self.world_size == 1:
            # A one-process group: any permutation is a self-loop (or drop,
            # which ppermute defines as zeros — with one rank only (0,0) exists).
            return np.asarray(tensor) if perm else np.zeros_like(np.asarray(tensor))
        garr = self._to_group_array(tensor)
        fn = self._compiled("sendrecv", ReduceOp.SUM, garr.shape, garr.dtype, tuple(perm))
        return np.asarray(fn(garr).addressable_shards[0].data)[0]

    # ------------------------------------------------------------------ local-device variants
    # The analogue of the reference's *_multigpu calls
    # (`collective.py allreduce_multigpu:258+`): one process driving N chips.
    def allreduce_multidevice(self, tensors: List, op: ReduceOp = ReduceOp.SUM):
        import jax
        from jax.sharding import PartitionSpec as P

        arr = self._shard_over_local(tensors)
        red = _psum_like(op, "local")
        fn = self._cache.get(("ar_md", op, arr.shape, str(arr.dtype)))
        if fn is None:
            # Per-device block keeps a leading length-1 stack dim; drop it so the
            # result has each contribution's own shape.
            fn = jax.jit(
                jax.shard_map(
                    lambda x: red(x)[0], mesh=self.mesh, in_specs=P("local"),
                    out_specs=P(), check_vma=False,
                )
            )
            self._cache[("ar_md", op, arr.shape, str(arr.dtype))] = fn
        out = np.asarray(fn(arr))
        return [out for _ in tensors]

    def allgather_multidevice(self, tensors: List):
        import jax
        from jax.sharding import PartitionSpec as P

        arr = self._shard_over_local(tensors)
        fn = self._cache.get(("ag_md", arr.shape, str(arr.dtype)))
        if fn is None:
            fn = jax.jit(
                jax.shard_map(
                    lambda x: jax.lax.all_gather(x, "local", axis=0, tiled=True),
                    mesh=self.mesh,
                    in_specs=P("local"),
                    out_specs=P(),
                    check_vma=False,
                )
            )
            self._cache[("ag_md", arr.shape, str(arr.dtype))] = fn
        out = np.asarray(fn(arr))
        return [out[i] for i in range(len(tensors))]

    def reducescatter_multidevice(self, tensors: List, op: ReduceOp = ReduceOp.SUM):
        import jax
        from jax.sharding import PartitionSpec as P

        arr = self._shard_over_local(tensors)
        fn = self._cache.get(("rs_md", op, arr.shape, str(arr.dtype)))
        if fn is None:
            fn = jax.jit(
                jax.shard_map(
                    # x is (1, *shape): drop the stack dim, then scatter the
                    # contribution's own leading dim across devices.
                    lambda x: jax.lax.psum_scatter(x[0], "local", scatter_dimension=0, tiled=True),
                    mesh=self.mesh,
                    in_specs=P("local"),
                    out_specs=P("local"),
                    check_vma=False,
                )
            )
            self._cache[("rs_md", op, arr.shape, str(arr.dtype))] = fn
        out = fn(arr)
        return [np.asarray(s.data) for s in out.addressable_shards]

    def destroy(self):
        if self.world_size > 1 and self.rank == 0 and self._kv is not None:
            clear(self._kv, f"collective/{self.group_name}/jax_coordinator".encode())
