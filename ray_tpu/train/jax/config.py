"""JaxConfig/_JaxBackend: gang-wide jax.distributed bring-up + mesh plumbing.

Reference seam: `python/ray/train/torch/config.py` — `_TorchBackend.on_start`
(`:155`) runs `_setup_torch_process_group` (`:69`) with rank 0 as master. Here
rank 0's host:port becomes the jax coordinator; every worker enters
`jax.distributed.initialize(coordinator, num_processes, process_id)`
concurrently (it blocks until the full gang joins — the same all-or-nothing
gang semantics, SURVEY.md §7).

After on_start, each worker's `jax.devices()` spans the whole gang. The mesh
builder (run inside the session thread) reshapes the global device list into
the `ScalingConfig.mesh` axes (`MeshSpec`, axis order tensor-innermost so TP
collectives ride the fastest ICI links).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

import ray_tpu
from ray_tpu.air.config import ScalingConfig
from ray_tpu.train.backend import Backend, BackendConfig


# libtpu's default port for the first process of a host; process k listens on
# base + k (its chip index).
_TPU_PROCESS_PORT_BASE = 8476


def _chip_grant() -> Dict[str, Any]:
    """This worker's host and the chips of it the scheduler granted."""
    import os
    import socket

    from ray_tpu._private.accelerators import tpu as tpu_accel

    visible = os.environ.get("TPU_VISIBLE_CHIPS", "")
    return {
        "host": socket.gethostname(),
        "chips": [int(c) for c in visible.split(",") if c],
        "host_chips": tpu_accel.detect_num_tpu_chips(),
    }


def _tpu_process_envs(grants: List[Dict[str, Any]]) -> List[Dict[str, str]]:
    """Per-worker libtpu environment that lets one-chip processes sharing a
    host form ONE topology (the multi-host model in miniature).

    A worker that is alone on its host needs nothing: it drives its chips as
    a host of its own and, across hosts, libtpu joins the slice from the TPU
    VM's metadata. Workers that share a host must tell libtpu: each is one
    process of a `host_chips`-process grid, reachable on its own port. The
    formation verified on the v5e host with libtpu 0.0.34 is every chip of
    the host, one per worker (4 x 1 on the 2x2 host); others are refused
    rather than guessed.
    """
    from ray_tpu._private.accelerators import tpu as tpu_accel

    by_host: Dict[str, List[int]] = {}
    for i, g in enumerate(grants):
        by_host.setdefault(g["host"], []).append(i)
    envs: List[Dict[str, str]] = [{} for _ in grants]
    for host, members in by_host.items():
        if len(members) == 1:
            continue
        host_chips = grants[members[0]]["host_chips"]
        held = sorted(c for i in members for c in grants[i]["chips"])
        if (
            len(by_host) > 1
            or any(len(grants[i]["chips"]) != 1 for i in members)
            or held != list(range(host_chips))
        ):
            raise RuntimeError(
                f"TPU gang formation not supported: {len(members)} workers on "
                f"host {host} hold chips {held} of {host_chips}. Workers that "
                "share a host must be one host's worth of one-chip workers "
                "(num_workers == chips of the host, tpus_per_worker=1); "
                "otherwise give each host one worker with "
                "tpus_per_worker=<chips of the host>."
            )
        ports = {i: _TPU_PROCESS_PORT_BASE + grants[i]["chips"][0] for i in members}
        addresses = ",".join(f"localhost:{ports[i]}" for i in sorted(members, key=ports.get))
        for i in members:
            envs[i] = {
                "TPU_PROCESS_BOUNDS": tpu_accel.process_bounds(host_chips),
                "TPU_PROCESS_ADDRESSES": addresses,
                "TPU_PROCESS_PORT": str(ports[i]),
                "CLOUD_TPU_TASK_ID": str(grants[i]["chips"][0]),
            }
    return envs


def _start_jax(coordinator: Optional[str], num_processes: int, process_id: int,
               granted_chips: int, tpu_env: Dict[str, str],
               trace: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Runs on every worker before any user code: compile cache, gang join,
    and — for a worker granted chips — proof that jax came up on them. Each
    of the three is a span (`trace` says where they hang), returned under
    `spans` beside the device's report."""
    import os

    from ray_tpu._private.accelerators import jax_process
    from ray_tpu.train._internal.telemetry import (
        DISTRIBUTED_INIT_SPAN, SpanLog, span_seconds)

    log = SpanLog.from_wire(trace, process_id)
    os.environ.update(tpu_env)
    with log.span("ray_tpu.train.worker.import_jax"):
        jax_process.configure_compile_cache()
        import jax
    if coordinator is not None:
        from ray_tpu.util.collective import rendezvous

        # initialize() blocks until every process joins — a gang rendezvous.
        # The span's seconds are the goodput ledger's rendezvous_wait share
        # of bring-up, and this process's accumulator takes the same number.
        try:
            with log.span(DISTRIBUTED_INIT_SPAN) as span:
                jax.distributed.initialize(
                    coordinator_address=coordinator,
                    num_processes=num_processes,
                    process_id=process_id,
                )
        finally:
            rendezvous.note_wait(span_seconds(span))
    report: Dict[str, Any] = {}
    if granted_chips or coordinator is not None:
        # The first jax.local_devices(): libtpu opens the chip.
        with log.span("ray_tpu.train.worker.device_touch",
                      TPU_VISIBLE_CHIPS=os.environ.get("TPU_VISIBLE_CHIPS", "")) as span:
            report = (jax_process.require_granted_chips(granted_chips) if granted_chips
                      else jax_process.device_report())
            span["attributes"].update(platform=report["platform"],
                                      local_devices=report["local_devices"])
    # A lone CPU worker leaves the backend to the user loop.
    return {**report, "spans": log.take()}


def _shutdown_jax_distributed():
    import jax

    try:
        jax.distributed.shutdown()
    except Exception:
        pass


def _build_mesh(mesh_axes: Optional[Dict[str, int]]):
    """Session-thread mesh builder: global devices -> jax.sharding.Mesh."""
    import jax

    from ray_tpu.parallel import MeshSpec

    devices = jax.devices()
    if mesh_axes:
        spec = MeshSpec.from_dict(mesh_axes)
        if spec.num_devices != len(devices):
            raise ValueError(
                f"ScalingConfig.mesh {mesh_axes} wants {spec.num_devices} devices "
                f"but the gang has {len(devices)}"
            )
    else:
        spec = MeshSpec.for_data_parallel(len(devices))
    return spec.build(devices)


@dataclass
class JaxConfig(BackendConfig):
    """Backend config for JAX SPMD training.

    distributed: force multi-controller bring-up on/off (default: automatic —
      on iff the gang has more than one worker).
    """

    distributed: Optional[bool] = None

    @property
    def backend_cls(self):
        return _JaxBackend

    def mesh_builder(self, scaling_config: ScalingConfig) -> Callable:
        spec = scaling_config.mesh_spec()
        axes = None
        if spec is not None:
            from ray_tpu.parallel import AXIS_ORDER

            axes = {a: s for a, s in zip(AXIS_ORDER, spec.shape) if s > 1}
        return functools.partial(_build_mesh, axes)


class _JaxBackend(Backend):
    def on_start(self, executor, backend_config: JaxConfig):
        wg = executor.worker_group
        n = len(wg)
        distributed = (
            backend_config.distributed
            if backend_config.distributed is not None
            else n > 1
        )
        granted = int(executor._scaling._resources.get("TPU", 0))
        tpu_envs: List[Dict[str, str]] = [{} for _ in range(n)]
        coordinator = None
        rank_of = executor.ranks
        seam = {"distributed": distributed, "granted": granted}
        executor.span_attributes(**seam)  # on the `backend` span round all of this
        with executor.span("backend.chip_grant", **seam):
            if granted and distributed:
                tpu_envs = _tpu_process_envs(wg.execute(_chip_grant))
            if distributed:
                # Rank 0's node hosts the jax coordination service.
                rank0_index = rank_of.index(0)
                meta = wg._metadata or wg.fetch_metadata()
                port = wg.execute_single(rank0_index, _free_port_fn)
                coordinator = f"{meta[rank0_index].node_ip}:{port}"
        # All workers must enter initialize() together: fire async, then gather.
        with executor.span("backend.start_jax", **seam):
            trace = executor.worker_trace()
            reports = ray_tpu.get([
                w.execute.remote(
                    _start_jax, coordinator, n, rank_of[i], granted, tpu_envs[i], trace
                )
                for i, w in enumerate(wg.workers)
            ])
        for r in reports:
            executor.note_worker_spans(r.pop("spans"))
        if distributed:
            counts = [r["global_devices"] for r in reports]
            want = n * granted if granted else counts[0]
            if set(counts) != {want}:
                raise RuntimeError(
                    f"workers disagree on the global device count: {counts} "
                    f"(expected {want} from {n} workers)"
                )

    def on_shutdown(self, executor, backend_config: JaxConfig):
        if executor.worker_group is not None:
            try:
                executor.worker_group.execute(_shutdown_jax_distributed)
            except Exception:
                pass


def _free_port_fn() -> int:
    import socket

    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port
