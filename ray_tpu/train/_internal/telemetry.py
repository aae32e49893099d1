"""Worker-side step clock for training-gang observability.

Every training step is split into named phases. The user loop marks the
explicit seams (`air.session.mark_phase("data_wait")` before pulling a batch,
`"compile"` around a cold jit, ...); the framework fills in the automatic
ones: collective time is folded out of the enclosing phase using the
`util.collective` per-process accumulators, the seconds jax spent tracing,
lowering and compiling (`jax_process.COMPILE_TOTALS`) are folded out of the
phase they ran inside and into "compile", and the result hand-off to the
driver (the bounded-queue put in `session.report`, i.e. driver backpressure)
is accrued as the "report" phase — "checkpoint" when a checkpoint rides the
report.

The seams of a gang's bring-up are spans too (`SpanLog`): plain dicts of
`util/tracing.py`, kind "bringup", children of one `ray_tpu.train.fit` root a
`fit()`. They are kept whenever a goodput ledger exists and cost a pair of
`time.time()` calls each, nothing per step.

Per step the clock emits one `ray_tpu_train_step_seconds{phase,gang,rank}`
histogram sample per non-empty phase (behind `enable_metrics`) and one
"train_step" span (behind `enable_timeline`/tracing). The span is started
non-detached in the session thread, so collective/transfer spans opened by
the step body parent under it automatically. The per-step telemetry dict is
attached to each REPORT `TrainingResult`; the driver's BackendExecutor folds
gang-wide dicts into the skew report and goodput ledger.

Phase accounting is conservation-exact within a step: phases partition the
step wall time (collective time is *moved* from the phase it accrued inside,
never double-counted), so the driver can ledger gang wall time to >=95%
without guessing.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, Iterator, List, Optional

from ray_tpu._private.accelerators.jax_process import COMPILE_TOTALS

# Step phases, in rough step order. "step_exec" is the default bucket: time
# not explicitly marked (and not claimed by an automatic seam) is compute.
PHASES = ("data_wait", "compile", "step_exec", "collective", "report", "checkpoint")

# Phases collective time can have accrued inside (same thread, so it is a
# slice of whatever phase was current when the op ran).
_COLLECTIVE_DONORS = ("step_exec", "data_wait", "compile", "checkpoint")


def _coll_snap():
    from ray_tpu.util.collective import collective

    return (
        collective._STATS["time_s"],
        collective._STATS["arrival_offset_s"],
    )


def _rdzv_snap() -> float:
    from ray_tpu.util.collective import rendezvous

    return rendezvous._WAIT_STATS["wait_s"]


# What a step's telemetry and a span carry of the compile counter: counts and
# seconds of the three kinds, and the persistent cache's hits and misses.
_COMPILE_KEYS = ("traces", "trace_s", "lowerings", "lower_s", "compiles",
                 "backend_s", "cache_hits", "cache_misses")


def compile_delta(before: Dict[str, float]) -> Dict[str, float]:
    """What the compile counter gained since `before` (a copy of
    `COMPILE_TOTALS`), the keys that moved alone."""
    return {k: round(COMPILE_TOTALS[k] - before[k], 6) if k.endswith("_s")
            else COMPILE_TOTALS[k] - before[k]
            for k in _COMPILE_KEYS if COMPILE_TOTALS[k] != before[k]}


# ------------------------------------------------------- bring-up spans
BRINGUP = "bringup"
ROOT_SPAN = "ray_tpu.train.fit"
DISTRIBUTED_INIT_SPAN = "ray_tpu.train.worker.distributed_init"  # the gang join


def span_seconds(span: Dict[str, Any]) -> float:
    return max(0.0, (span.get("end") or span["start"]) - span["start"])


class SpanLog:
    """The bring-up spans one process records for one gang: the dicts
    `tracing.start_span` makes, each a child of the span that caused it.
    `context` is where this process's spans hang (the root's, or the span of
    the driver that started this worker); spans opened inside `span()` nest.
    With `push` the finished spans also go to the head's ring, for
    `ray_tpu.timeline()`; either way they stay in `spans`, for the ledger."""

    def __init__(self, gang: str, context: Optional[Dict[str, str]] = None,
                 push: bool = False, rank: Optional[int] = None):
        self.gang = gang
        self.context = context
        self.push = push
        self.rank = rank
        self.spans: List[dict] = []
        self._open: List[dict] = []

    @classmethod
    def from_wire(cls, wire: Optional[Dict[str, Any]], rank: int) -> "SpanLog":
        wire = wire or {}
        return cls(wire.get("gang", ""), wire.get("context"),
                   bool(wire.get("push")), rank)

    @property
    def innermost(self) -> Optional[dict]:
        """The innermost span open under `span()`, if any."""
        return self._open[-1] if self._open else None

    def wire(self) -> Dict[str, Any]:
        """What a worker needs to hang its spans under the innermost open one."""
        from ray_tpu.util import tracing

        parent = tracing.context_of(self.innermost) if self._open else self.context
        return {"gang": self.gang, "context": parent, "push": self.push}

    def open(self, name: str, parent: Optional[dict] = None, **attributes: Any) -> dict:
        from ray_tpu.util import tracing

        attrs: Dict[str, Any] = {"gang": self.gang}
        if self.rank is not None:
            attrs["rank"] = self.rank
        attrs.update(attributes)
        if parent is None:
            parent = self.innermost
        parent = tracing.context_of(parent) if parent is not None else self.context
        # A root here is one a fit(): never a sampling draw's to drop.
        return tracing.start_span(name, BRINGUP, trace_context=parent,
                                  attributes=attrs, detached=True, presampled=True)

    def close(self, span: dict, status: str = "OK", end: Optional[float] = None,
              **attributes: Any) -> dict:
        from ray_tpu.util import tracing

        span["attributes"].update(attributes)
        if self.push:
            tracing.end_span(span, status, end)
        else:
            span["end"] = time.time() if end is None else end
            span["status"] = status
            span.pop("_detached", None)
        self.spans.append(span)
        return span

    def record(self, name: str, start: float, end: float, parent: dict,
               **attributes: Any) -> dict:
        """A span whose two ends were read elsewhere (another process's clock
        marks, carried home in a reply), under `parent`."""
        span = self.open(name, parent, **attributes)
        span["start"] = start
        return self.close(span, end=end)

    @contextlib.contextmanager
    def span(self, name: str, **attributes: Any) -> Iterator[dict]:
        span = self.open(name, **attributes)
        self._open.append(span)
        try:
            yield span
        except BaseException:
            self._open.remove(span)
            self.close(span, "ERROR")
            raise
        self._open.remove(span)
        self.close(span)

    def take(self) -> List[dict]:
        """The finished spans as they travel in a reply, and forget them."""
        from ray_tpu.util import tracing

        out, self.spans = [tracing._strip(s) for s in self.spans], []
        return out


class StepClock:
    """Accrues wall time into the current phase; closed once per report.

    Thread discipline: construct and drive from the session thread only (the
    thread running train_fn) — the train_step span relies on that thread's
    tracing context, and the collective accumulators it diffs are bumped by
    the same thread.
    """

    def __init__(self, gang: str, rank: int):
        from ray_tpu._private.config import get_config
        from ray_tpu.util import tracing

        cfg = get_config()
        self.gang = gang or "default"
        self.rank = str(rank)
        self.metrics_on = bool(cfg.enable_metrics)
        self._want_span = bool(cfg.enable_timeline) or tracing.is_enabled()
        now = time.perf_counter()
        self._wall_t0 = now
        self._steps = 0
        self._totals: Dict[str, float] = {p: 0.0 for p in PHASES}
        self._total_rdzv = 0.0
        self._total_offset = 0.0
        self._span = None
        self._closed = False
        self._compile_mark = COMPILE_TOTALS["seconds"]
        # The counter as it stood when it last moved before a step began.
        self._compile_t0: Dict[str, float] = dict(COMPILE_TOTALS)
        self._begin_step(now)

    # ------------------------------------------------------------ internals
    def _begin_step(self, now: float) -> None:
        self._step_t0 = now
        self._phase = "step_exec"
        self._phase_t0 = now
        self._acc: Dict[str, float] = {p: 0.0 for p in PHASES}
        # Seconds the compile counter gained inside each phase of this step.
        self._compiled_in: Dict[str, float] = {}
        if COMPILE_TOTALS["events"] != self._compile_t0["events"]:
            self._compile_t0 = dict(COMPILE_TOTALS)
        self._coll_t0, self._off_t0 = _coll_snap()
        self._rdzv_t0 = _rdzv_snap()
        if self._want_span:
            from ray_tpu.util import tracing

            self._span = tracing.start_span(
                "train_step",
                "train",
                attributes={
                    "gang": self.gang,
                    "rank": self.rank,
                    "step": str(self._steps),
                },
            )

    def _accrue(self, now: float) -> None:
        self._acc[self._phase] += now - self._phase_t0
        self._phase_t0 = now
        seconds = COMPILE_TOTALS["seconds"]
        if seconds != self._compile_mark:
            self._compiled_in[self._phase] = (
                self._compiled_in.get(self._phase, 0.0) + seconds - self._compile_mark)
            self._compile_mark = seconds

    def _fold_compile(self) -> None:
        """Move what jax traced, lowered and compiled out of the phase it ran
        inside and into "compile". What ran under `mark_phase("compile")` is
        there already: counted once."""
        for phase, seconds in self._compiled_in.items():
            if phase == "compile":
                continue
            take = min(self._acc[phase], seconds)
            self._acc[phase] -= take
            self._acc["compile"] += take

    def _fold_collective(self) -> None:
        """Move collective wall time out of the phase(s) it ran inside."""
        coll_t, _ = _coll_snap()
        coll_d = max(0.0, coll_t - self._coll_t0)
        if coll_d <= 0.0:
            return
        donor = max(_COLLECTIVE_DONORS, key=lambda p: self._acc[p])
        take = min(self._acc[donor], coll_d)
        self._acc[donor] -= take
        self._acc["collective"] += take

    # ------------------------------------------------------------ public
    def mark(self, phase: str) -> None:
        if phase not in PHASES:
            raise ValueError(
                f"unknown training phase {phase!r}; one of {PHASES}"
            )
        self._accrue(time.perf_counter())
        self._phase = phase

    def close_step(self, *, checkpoint: bool = False) -> Dict[str, Any]:
        """Close the current step and return its telemetry dict. The caller
        hands the result to the driver afterwards, bracketed by
        mark("report"/"checkpoint") ... mark("step_exec"): the queue-put wait
        (driver backpressure) lands in the next step's report phase, keeping
        totals exact without racing the driver for the result object."""
        now = time.perf_counter()
        self._accrue(now)
        compiled = (compile_delta(self._compile_t0)
                    if COMPILE_TOTALS["events"] != self._compile_t0["events"] else None)
        self._fold_compile()
        self._fold_collective()
        step_wall = now - self._step_t0
        _, off_t = _coll_snap()
        rdzv_d = max(0.0, _rdzv_snap() - self._rdzv_t0)
        off_d = max(0.0, off_t - self._off_t0)
        self._steps += 1
        for p, v in self._acc.items():
            self._totals[p] += v
        self._total_rdzv += rdzv_d
        self._total_offset += off_d
        telem = {
            "step": self._steps,
            "step_wall_s": step_wall,
            "phases": {p: v for p, v in self._acc.items() if v > 0.0},
            "rendezvous_wait_s": rdzv_d,
            "arrival_offset_s": off_d,
        }
        if compiled:
            telem["compile"] = compiled
        if self.metrics_on:
            from ray_tpu._private.telemetry import train_metrics

            hist = train_metrics()["step_seconds"]
            for p, v in self._acc.items():
                if v > 0.0:
                    hist.observe(v, {"phase": p, "gang": self.gang, "rank": self.rank})
        if self._span is not None:
            from ray_tpu.util import tracing

            tracing.end_span(self._span)
            self._span = None
        self._begin_step(now)
        return telem

    def snapshot(self) -> Dict[str, Any]:
        """Live cumulative view (driver-pollable; does not close anything)."""
        return {
            "gang": self.gang,
            "rank": int(self.rank),
            "steps": self._steps,
            "wall_s": time.perf_counter() - self._wall_t0,
            "phases": dict(self._totals),
            "rendezvous_wait_s": self._total_rdzv,
            "arrival_offset_s": self._total_offset,
        }

    def finalize(self) -> Dict[str, Any]:
        """Close out the session: accrue the tail, end any open span, return
        cumulative totals. Safe to call once from the session thread's
        finally block; later calls return the frozen totals."""
        if self._closed:
            return self.snapshot()
        self._closed = True
        now = time.perf_counter()
        self._accrue(now)
        self._fold_compile()
        self._fold_collective()
        for p, v in self._acc.items():
            self._totals[p] += v
        self._acc = {p: 0.0 for p in PHASES}
        if self._span is not None:
            from ray_tpu.util import tracing

            tracing.end_span(self._span)
            self._span = None
        out = self.snapshot()
        out["wall_s"] = now - self._wall_t0
        # Process-lifetime rendezvous seconds: includes gang-join waits that
        # happened before this clock existed (jax.distributed.initialize runs
        # in on_start, ahead of init_session) — the ledger wants those too.
        out["rendezvous_wait_total_s"] = _rdzv_snap()
        # And the process's compile counter, whole: the first compiles ran
        # before the first step closed.
        from ray_tpu._private.accelerators import jax_process

        out["compile"] = jax_process.compile_stats()
        return out


def make_clock(gang: str, rank: int) -> Optional[StepClock]:
    """A StepClock when any observability sink is on, else None (the session
    skips all bookkeeping so knob-off training pays nothing)."""
    from ray_tpu._private.config import get_config
    from ray_tpu.util import tracing

    cfg = get_config()
    if not (cfg.enable_metrics or cfg.enable_timeline or tracing.is_enabled()):
        return None
    return StepClock(gang, rank)
