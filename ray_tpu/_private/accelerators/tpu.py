"""TPU detection and topology, the accelerator module the reference lacks entirely
(its `resource_spec.py:173-178` autodetects only CPU/mem/GPU; `_autodetect_num_gpus`
at `:268` counts /proc/driver/nvidia — SURVEY.md P3 flags "no TPU detection
anywhere"). This module is the TPU analogue: chips become a schedulable `TPU`
resource, and slice topology (from TPU-VM env metadata) feeds the topology-aware
placement-group policy.
"""

from __future__ import annotations

import glob
import math
import os
import re
from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple

# Generation -> chips with wraparound torus links when a full cube is used.
_TPU_VERSION_PATTERN = re.compile(r"^(v\d+[a-z]*)(?:-(\d+))?$")


# One device file per chip this host can open: `/dev/accel<n>` on TPU VMs with
# the accel driver, one numbered VFIO group per chip otherwise.
_DEVICE_FILES = ("/dev/accel*", "/dev/vfio/[0-9]*")


def detect_num_tpu_chips() -> int:
    """Count the TPU chips this host can open, without initializing any runtime.

    Order: `RAY_TPU_NUM_CHIPS` (explicit override) -> device files
    (`/dev/accel*`, then `/dev/vfio/<group>`) -> the TPU VM's
    `TPU_CHIPS_PER_HOST_BOUNDS`. Device files come before the metadata
    because a VM can describe a four-chip host and pass through one chip: the
    v5e machines this repo runs on export `TPU_CHIPS_PER_HOST_BOUNDS=2,2,1`
    and list four Google PCI functions either way, while `/dev/vfio/` holds
    one numbered group per chip that libtpu can actually open (1 or 4).
    (Importing jax here would grab the chips; detection must stay passive.)
    """
    override = os.environ.get("RAY_TPU_NUM_CHIPS")
    if override:
        try:
            return int(override)
        except ValueError:
            pass
    for pattern in _DEVICE_FILES:
        found = glob.glob(pattern)
        if found:
            return len(found)
    return math.prod(chips_per_host_bounds() or (0,))


def detection_report() -> str:
    """What `detect_num_tpu_chips` looked at, for the error a chipless
    `use_tpu=True` run ends in."""
    files = ", ".join(f"{p}: {len(glob.glob(p))}" for p in _DEVICE_FILES)
    return (
        f"RAY_TPU_NUM_CHIPS={os.environ.get('RAY_TPU_NUM_CHIPS')!r}, {files}, "
        f"TPU_CHIPS_PER_HOST_BOUNDS={os.environ.get('TPU_CHIPS_PER_HOST_BOUNDS')!r}"
    )


# Chips one process may drive, and libtpu's (x, y, z) box for that many. A
# grant is an aligned block of chip indices ([0,1] or [2,3], never [1,2]) so
# that the box is a contiguous piece of the host's chip grid.
_PROCESS_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}

# Every variable a chip grant sets. A worker without a grant, and a worker
# with a different one, must not inherit any of them.
CHIP_ENV_VARS = (
    "TPU_VISIBLE_CHIPS",
    "TPU_CHIPS_PER_PROCESS_BOUNDS",
    "TPU_PROCESS_BOUNDS",
    "TPU_PROCESS_ADDRESSES",
    "TPU_PROCESS_PORT",
    "CLOUD_TPU_TASK_ID",
)


def valid_chip_count(n: float) -> bool:
    return n in _PROCESS_BOUNDS


def process_bounds(chips: int) -> str:
    """libtpu's `x,y,z` box for `chips` chips (or one-chip processes)."""
    return _PROCESS_BOUNDS[chips]


def take_chips(free: list, n: int) -> Optional[Tuple[int, ...]]:
    """Remove and return the lowest aligned block of `n` chip indices from
    `free`, or None when no such block is free (the request waits: a chip is
    never handed to two processes)."""
    have = set(free)
    for base in sorted(c for c in have if c % n == 0):
        block = tuple(range(base, base + n))
        if have.issuperset(block):
            for c in block:
                free.remove(c)
            return block
    return None


def chip_env(chips: Sequence[int]) -> Dict[str, str]:
    """The environment of a worker process granted `chips`: libtpu opens
    exactly these and presents them as a host of its own. With no grant the
    process is pinned off the accelerator — jax would otherwise open every
    chip of the host on first use and hold them while idle."""
    if not chips:
        return {"JAX_PLATFORMS": "cpu"}
    return {
        "TPU_VISIBLE_CHIPS": ",".join(str(c) for c in chips),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": process_bounds(len(chips)),
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


@dataclass
class TpuTopology:
    """A pod slice's shape in chips, e.g. v4-32 = (4, 4, 2) with 4 chips/host."""

    generation: str  # "v4", "v5e", ...
    num_chips: int
    chips_per_host: int
    mesh_shape: tuple  # physical chip grid

    @property
    def num_hosts(self) -> int:
        return max(1, self.num_chips // self.chips_per_host)

    def has_wraparound(self) -> bool:
        """v4/v5p tori have wraparound ICI links when each dim is a multiple of 4
        (the cube constraint the scaling literature describes); this feeds ring
        collective layout choices."""
        return all(d >= 4 and d % 4 == 0 for d in self.mesh_shape if d > 1)


_KNOWN = {
    # accelerator_type -> (chips_per_host, dims fn)
    "v2": 4,
    "v3": 4,
    "v4": 4,
    "v5p": 4,
    "v5e": 4,  # actually 1/4/8 depending on VM shape; 4 is the common default
    "v5litepod": 4,
    "v6e": 4,
}


def detect_topology() -> Optional[TpuTopology]:
    """Parse TPU VM metadata env vars (TPU_ACCELERATOR_TYPE, e.g. "v4-32")."""
    accel_type = os.environ.get("TPU_ACCELERATOR_TYPE") or os.environ.get(
        "ACCELERATOR_TYPE"
    )
    if not accel_type:
        n = detect_num_tpu_chips()
        if n == 0:
            return None
        return TpuTopology("unknown", n, n, (n,))
    m = _TPU_VERSION_PATTERN.match(accel_type.lower())
    if not m:
        return None
    gen = m.group(1)
    cores = int(m.group(2) or 0)
    # v2/v3 count cores (2/chip); v4+ count chips for pods.
    chips = cores // 2 if gen in ("v2", "v3") else cores
    chips = max(chips, 1)
    cph = _KNOWN.get(gen, 4)
    topo_env = os.environ.get("TPU_TOPOLOGY")  # e.g. "4x4x2"
    if topo_env:
        mesh = tuple(int(x) for x in topo_env.lower().split("x"))
    else:
        mesh = (chips,)
    return TpuTopology(gen, chips, cph, mesh)


def tpu_pod_name() -> Optional[str]:
    return os.environ.get("TPU_NAME") or os.environ.get("TPU_POD_NAME")


def worker_id() -> int:
    try:
        return int(os.environ.get("TPU_WORKER_ID", "0"))
    except ValueError:
        return 0


def _parse_bounds(raw: Optional[str]) -> Optional[tuple]:
    if not raw:
        return None
    try:
        return tuple(int(x) for x in raw.replace("x", ",").split(","))
    except ValueError:
        return None


def chips_per_host_bounds() -> Optional[tuple]:
    """Per-host chip block, e.g. a v4 host drives 2x2x1 chips. libtpu exports
    this as TPU_CHIPS_PER_HOST_BOUNDS (NOT TPU_HOST_BOUNDS, which is the
    host-grid layout — detect_num_tpu_chips above uses the same convention)."""
    return _parse_bounds(os.environ.get("TPU_CHIPS_PER_HOST_BOUNDS"))


def host_grid_bounds() -> Optional[tuple]:
    """Host-grid layout of the slice (hosts per dim): TPU_HOST_BOUNDS, e.g.
    "2,2,2" for a v4-32's 8 hosts."""
    return _parse_bounds(os.environ.get("TPU_HOST_BOUNDS"))


def node_topology_labels() -> dict:
    """Labels describing this host's position in its TPU slice, attached to the
    node at registration so the TPU_SLICE placement policy
    (`util/tpu_topology_policy.py`) can select contiguous sub-boxes of hosts.
    Empty dict off-TPU (or for single-host slices with no topology metadata)."""
    topo = detect_topology()
    if topo is None or len(topo.mesh_shape) < 2:
        return {}
    labels = {
        "tpu_topology": "x".join(str(d) for d in topo.mesh_shape),
        "tpu_generation": topo.generation,
    }
    pod = tpu_pod_name()
    if pod:
        labels["tpu_pod_name"] = pod
    from ray_tpu.util.tpu_topology_policy import (
        coord_for_worker,
        format_coord,
        host_grid,
    )

    # Host grid: prefer the direct layout (TPU_HOST_BOUNDS), else derive it
    # from the chip mesh / per-host chip block.
    grid = host_grid_bounds()
    if grid is None or len(grid) != len(topo.mesh_shape):
        hb = chips_per_host_bounds()
        if hb is None and len(topo.mesh_shape) == 3:
            hb = (2, 2, 1)  # v4/v5p standard host block
        if hb is None or len(hb) != len(topo.mesh_shape):
            return labels
        try:
            grid = host_grid(topo.mesh_shape, hb)
        except ValueError:
            return labels
    labels["tpu_host_grid"] = "x".join(str(d) for d in grid)
    coord_env = os.environ.get("TPU_HOST_COORD")
    coord = (
        tuple(int(x) for x in coord_env.split(","))
        if coord_env
        else coord_for_worker(worker_id(), grid)
    )
    labels["tpu_host_coord"] = format_coord(coord)
    return labels
