"""What every process that runs jax on a chip does first: place the
persistent compile cache, start counting what jax traces, lowers and compiles,
and prove the device it was promised.

Kept apart from `tpu.py`, which must stay importable without jax (detection
is passive: importing jax in the head would take the chips). The counter's
dicts are plain module state: reading them imports nothing.
"""

from __future__ import annotations

import os
import threading
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
    _count_compiles()
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE_DIR)
    return _DEFAULT_CACHE_DIR


# ------------------------------------------------------- the compile counter
# jax announces each trace of a jitted function, each lowering to MLIR and
# each backend compile (a retrieval from the persistent cache included) on
# `jax.monitoring`, with the function's name. A frame's seconds are its own:
# what a nested frame took (a jit traced inside a trace, an eager op compiled
# inside one) belongs to that frame, so the three kinds add up to the wall
# time spent in any of them and a function is charged for itself alone.
_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": ("traces", "trace_s"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": ("lowerings", "lower_s"),
    "/jax/core/compile/backend_compile_duration": ("compiles", "backend_s"),
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
_CACHE_DURATIONS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read_s",
    "/jax/compilation_cache/compile_time_saved_sec": "cache_saved_s",
}
# `events` and `seconds` are what a step reads: one more event of the three
# kinds, and the seconds of all three together.
COMPILE_TOTALS: Dict[str, float] = {
    "events": 0, "seconds": 0.0,
    "traces": 0, "trace_s": 0.0, "lowerings": 0, "lower_s": 0.0,
    "compiles": 0, "backend_s": 0.0,
    "cache_hits": 0, "cache_misses": 0, "cache_read_s": 0.0, "cache_saved_s": 0.0,
}
_BY_FUNCTION: Dict[str, Dict[str, float]] = {}
_FUNCTIONS_KEPT = 256  # live names; a snapshot gives the 32 with most seconds
_FUNCTIONS_SHOWN = 32
_OTHER = "other"
_frames = threading.local()
_counter_lock = threading.Lock()
_counting = False


def function_seconds(row: Dict[str, float]) -> float:
    return row["trace_s"] + row["lower_s"] + row["backend_s"]


def _empty_row() -> Dict[str, float]:
    return {"traces": 0, "trace_s": 0.0, "lowerings": 0, "lower_s": 0.0,
            "compiles": 0, "backend_s": 0.0}


def _keep_costliest(rows: Dict[str, Dict[str, float]], keep: int) -> None:
    """Leave the `keep` functions with most seconds in `rows` and sum the
    rest into its `other`."""
    names = sorted((n for n in rows if n != _OTHER), key=lambda n: -function_seconds(rows[n]))
    for n in names[keep:]:
        other = rows.setdefault(_OTHER, _empty_row())
        for k, v in rows.pop(n).items():
            other[k] += v


def _row(name: str) -> Dict[str, float]:
    row = _BY_FUNCTION.get(name)
    if row is None:
        if len(_BY_FUNCTION) >= _FUNCTIONS_KEPT:
            # Eager ops bring a name each: fold the half that cost least.
            _keep_costliest(_BY_FUNCTION, _FUNCTIONS_KEPT // 2)
        row = _BY_FUNCTION[name] = _empty_row()
    return row


def _on_start(event: str, _value, **_kw) -> None:
    if event in _KINDS:
        stack = getattr(_frames, "stack", None)
        if stack is None:
            stack = _frames.stack = []
        stack.append(0.0)  # seconds of the frames nested in this one


def _on_duration(event: str, seconds: float, **kw) -> None:
    kind = _KINDS.get(event)
    if kind is None:
        key = _CACHE_DURATIONS.get(event)
        if key is not None:
            COMPILE_TOTALS[key] += seconds
        return
    stack = getattr(_frames, "stack", None)
    own = seconds - stack.pop() if stack else seconds
    if stack:
        stack[-1] += seconds
    own = max(0.0, own)
    count, secs = kind
    name = str(kw.get("fun_name") or "?")
    if name.startswith("jit(") and name.endswith(")"):
        name = name[4:-1]  # a lowering and a compile name the module: jit(<function>)
    with _counter_lock:
        row = _row(name)
        row[count] += 1
        row[secs] += own
        COMPILE_TOTALS[count] += 1
        COMPILE_TOTALS[secs] += own
        COMPILE_TOTALS["seconds"] += own
        COMPILE_TOTALS["events"] += 1


def _on_event(event: str, **_kw) -> None:
    key = _CACHE_EVENTS.get(event)
    if key is not None:
        COMPILE_TOTALS[key] += 1


def _count_compiles() -> None:
    """Register the listeners, once a process."""
    global _counting
    with _counter_lock:
        if _counting:
            return
        _counting = True
    from jax import monitoring

    monitoring.register_scalar_listener(_on_start)
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


def compile_stats() -> Dict[str, Any]:
    """What this process has traced, lowered and compiled since
    `configure_compile_cache()`: the totals, and per function (`fun_name` as
    jax gives it) the `_FUNCTIONS_SHOWN` with most seconds, the rest summed
    under `other`. All zero in a process that never counted."""
    with _counter_lock:
        totals = dict(COMPILE_TOTALS)
        rows = {n: dict(r) for n, r in _BY_FUNCTION.items()}
    _keep_costliest(rows, _FUNCTIONS_SHOWN)
    totals["functions"] = dict(sorted(rows.items(), key=lambda kv: -function_seconds(kv[1])))
    return totals


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
