"""What every process that runs jax on a chip does first: place the
persistent compile cache, and prove the device it was promised.

Kept apart from `tpu.py`, which must stay importable without jax (detection
is passive: importing jax in the head would take the chips).
"""

from __future__ import annotations

import os
from typing import Any, Dict

# <checkout>/.jax_cache: derived from the package location and nothing else.
# The path is part of what makes a cache findable by the next process, so it
# never contains a pid, a timestamp, a tempdir or the session directory.
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def configure_compile_cache() -> str:
    """Point jax's persistent compilation cache at its one place and return
    it. Called in every process that compiles (train workers,
    `benchmark/harness/worker.py`), before the first jit.

    `JAX_COMPILATION_CACHE_DIR`, when set, is read by jax itself and is left
    alone: no directory is set in code. Otherwise the cache lives at the
    fixed, git-ignored `<checkout>/.jax_cache`.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE_DIR)
    return _DEFAULT_CACHE_DIR


def device_report() -> Dict[str, Any]:
    """The device as jax reports it in this process (initializes the backend)."""
    import jax

    dev = jax.local_devices()[0]
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "local_devices": jax.local_device_count(),
        "global_devices": jax.device_count(),
        "process_index": jax.process_index(),
    }


def require_granted_chips(chips: int) -> Dict[str, Any]:
    """Raise unless this process's jax came up on exactly the `chips` TPU
    chips the scheduler granted it. jax only warns when libtpu cannot open a
    chip and carries on with the CPU backend; a worker that was promised a
    chip must not."""
    report = device_report()
    if report["platform"] != "tpu" or report["local_devices"] != chips:
        raise RuntimeError(
            f"this worker was granted {chips} TPU chip(s) "
            f"(TPU_VISIBLE_CHIPS={os.environ.get('TPU_VISIBLE_CHIPS')!r}) but "
            f"jax came up with {report['local_devices']} local "
            f"{report['platform']!r} device(s) ({report['device_kind']}); "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}. libtpu could "
            "not open the chip (held by another process?) or the platform is "
            "pinned elsewhere; refusing to train on a device that was not asked for."
        )
    return report
