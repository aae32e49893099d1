"""Stale-binary guard: a built native binary must match its source.

The `.so`s are not in git: they are built on demand, next to their sources,
on first use (`ray_tpu/_native/__init__.py`) and git-ignored. A binary left
in a working tree from an older source is the drift this catches: edit the
.c, keep the old .so, and a host whose rebuild fails silently runs the
previous decoder. The build flow stamps each binary with the sha256 of the
source it was built from (`-D*_SRC_SHA256`, exported as a greppable
``RAY_TPU_*_SRC_SHA256=<hex>`` marker string); this pass re-hashes the
source and compares — pure file reads, no dlopen, no runtime import.

A missing binary is NOT a violation (a fresh checkout has none); a binary
without a stamp is, and a stamp mismatch is the exact failure this exists
for.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Tuple

# The marker constants and the scan/hash helpers are the loader's own
# (ray_tpu._native defines the stamp format and self-heals on mismatch);
# importing them keeps the format in exactly ONE place. The import loads no
# .so — builds happen only inside load_arena_lib/load_wire_module.
from ray_tpu._native import (
    ARENA_HASH_MARKER, WIRE_HASH_MARKER, embedded_source_hash, source_sha256,
)
from ray_tpu.devtools.astutil import Violation, make_key
from ray_tpu.devtools.verify import DEFAULT_NATIVE_DIR

# binary -> (source, embedded marker prefix).
BINARIES: Dict[str, Tuple[str, bytes]] = {
    "wire_native.so": ("wire_native.c", WIRE_HASH_MARKER),
    "libshm_arena.so": ("shm_arena.cpp", ARENA_HASH_MARKER),
}


def run(pkg=None, native_dir: Optional[str] = None) -> List[Violation]:
    """`pkg` accepted (ignored) for pass-signature uniformity."""
    d = native_dir or DEFAULT_NATIVE_DIR
    violations: List[Violation] = []
    for so_name, (src_name, marker) in sorted(BINARIES.items()):
        so_path = os.path.join(d, so_name)
        src_path = os.path.join(d, src_name)
        if not os.path.exists(so_path) or not os.path.exists(src_path):
            continue  # binaries build on demand; nothing built yet to drift
        src_hash = source_sha256(src_path)
        got = embedded_source_hash(so_path, marker)
        if got is None:
            violations.append(Violation(
                "stale", so_path, 0,
                make_key("stale", so_path, "unstamped"),
                f"{so_name} carries no {marker.decode()!r} source stamp — "
                f"it predates the stale-binary guard; delete it and let it rebuild",
            ))
        elif got != src_hash:
            violations.append(Violation(
                "stale", so_path, 0,
                make_key("stale", so_path, "drift"),
                f"{so_name} was built from source {got[:12]}… but "
                f"{src_name} now hashes {src_hash[:12]}… — the built "
                f"binary is stale; delete it and let it rebuild",
            ))
    return violations
