"""Goodput ledger: classifies a training gang's wall time into productive
step time vs badput buckets, driver-side.

Every second of a fit() is claimed by exactly one bucket:

  productive      gang-mean step compute + collective time (real training)
  init            first gang bring-up (placement group, actors, backend
                  on_start) minus the rendezvous share below
  compile         gang-mean time in the "compile" phase (cold jit, mesh build)
  rendezvous_wait blocked joining the gang (jax.distributed.initialize,
                  collective KV rendezvous) — from the workers' rendezvous
                  wait accumulators
  checkpoint      gang-mean "checkpoint" phase + driver-side persist
                  (CheckpointManager.register)
  recover         failure detection + full gang restart after a
                  TrainingWorkerError
  resize          elastic membership change: drain at the step boundary,
                  re-rendezvous at the new world size, session re-init
                  (ISSUE 19 — resizes are not failures and not recover)
  idle            everything else: data_wait, report backpressure, driver
                  overhead between rounds

Accounting is interval-chained: the ledger keeps one monotonic mark and every
account_*/fold_round call classifies exactly the wall time since the previous
mark, so the buckets sum to the observed wall time by construction (coverage
~= 1.0; worker-reported phase splits are scaled down if clock skew makes them
exceed the driver-observed interval, never up).

The report also carries the gang's bring-up as spans (`bringup`: the seams of
`fit()` -> train_fn entered, driver's and every rank's, `telemetry.SpanLog`)
and what jax traced, lowered and compiled (`compile`: rank 0's counter with
its functions by seconds, and the gang's maximum of each total). The compile
bucket fills by itself: the workers' step clocks move the counter's seconds
into their "compile" phase.

The current report is published to the GCS KV under `train::<gang_id>` so
`state.training_report()`, the dashboard `/api/train`, and
`python -m ray_tpu train` can all read it without new wire plumbing. The final
one is also kept in the process that ran `fit()` (`kept_reports()`), for a
script that reads it after `ray_tpu.shutdown()`.
"""

from __future__ import annotations

import collections
import json
import time
from typing import Any, Dict, List, Optional

from ray_tpu._private.accelerators.jax_process import function_seconds
from ray_tpu.train._internal.telemetry import (
    DISTRIBUTED_INIT_SPAN, ROOT_SPAN, SpanLog, span_seconds)

BUCKETS = (
    "productive", "init", "compile", "rendezvous_wait",
    "checkpoint", "recover", "resize", "idle",
)

# Worker step-phase -> ledger bucket for the per-round fold. data_wait and
# report are driver/input-bound, not chip work: badput (idle).
_PHASE_BUCKET = {
    "step_exec": "productive",
    "collective": "productive",
    "compile": "compile",
    "checkpoint": "checkpoint",
    "data_wait": "idle",
    "report": "idle",
}

# Publish throttle: at most one KV write per this many seconds mid-run
# (finalize always publishes).
_PUBLISH_INTERVAL_S = 0.5

KV_PREFIX = b"train::"

# A report holds at most this many bring-up spans (a four-worker gang makes
# 31): the driver's own first, then the ranks' from rank 0 up.
MAX_BRINGUP_SPANS = 64

# Final reports of the last gangs this process ran fit() for, oldest first.
_KEPT: "collections.OrderedDict[str, Dict[str, Any]]" = collections.OrderedDict()
_KEPT_GANGS = 4


def report_key(gang: str) -> bytes:
    return KV_PREFIX + gang.encode()


def kept_reports() -> List[Dict[str, Any]]:
    """The final reports of the last `_KEPT_GANGS` gangs whose `fit()` ran in
    this process, oldest first. Plain data: asks no cluster, starts none."""
    return list(_KEPT.values())




def seconds_by_span(spans: List[Dict[str, Any]]) -> Dict[str, Dict[str, float]]:
    """name -> {"rank0": s, "max": s} over a report's `bringup`: a rank's span
    by its rank (summed over attempts), a driver's span as both."""
    by_rank: Dict[str, Dict[int, float]] = {}
    for s in spans:
        ranks = by_rank.setdefault(s["name"], {})
        rank = s["attributes"].get("rank", 0)
        ranks[rank] = ranks.get(rank, 0.0) + span_seconds(s)
    return {name: {"rank0": ranks.get(0, 0.0), "max": max(ranks.values())}
            for name, ranks in by_rank.items()}


def bringup_lines(report: Dict[str, Any], top: int = 5) -> List[str]:
    """A report's `bringup` and `compile` as the lines `python -m ray_tpu
    train` prints under a gang."""
    lines = []
    by_span = seconds_by_span(report.get("bringup") or [])
    if by_span:
        lines.append("bring-up, seconds by span (rank 0 / slowest rank):")
        for name, v in by_span.items():
            short = name[len("ray_tpu.train."):] if name.startswith("ray_tpu.train.") else name
            lines.append(f"  {short:<32} {v['rank0']:>9.3f} {v['max']:>9.3f}")
    c = (report.get("compile") or {}).get("rank0")
    if c:
        lines.append(
            f"compile (rank 0): {c['traces']} traces {c['trace_s']:.3f}s, "
            f"{c['lowerings']} lowerings {c['lower_s']:.3f}s, {c['compiles']} compiles "
            f"{c['backend_s']:.3f}s; cache {c['cache_hits']} hits {c['cache_misses']} misses, "
            f"read {c['cache_read_s']:.3f}s, saved {c['cache_saved_s']:.3f}s")
        rows = sorted(c.get("functions", {}).items(), key=lambda kv: -function_seconds(kv[1]))
        for name, r in rows[:top]:
            lines.append(
                f"  {name:<32} {function_seconds(r):>9.3f}s  traced {r['traces']}x "
                f"{r['trace_s']:.3f}s, lowered {r['lowerings']}x {r['lower_s']:.3f}s, "
                f"compiled {r['compiles']}x {r['backend_s']:.3f}s")
    return lines


class GoodputLedger:
    """One per fit(); survives gang restarts (recover is a bucket, not a new
    ledger). Driver-thread only."""

    def __init__(self, gang: str, world_size: int):
        self.gang = gang
        self.world_size = world_size
        self._wall_t0 = time.perf_counter()
        self._mark = self._wall_t0
        self.buckets: Dict[str, float] = {b: 0.0 for b in BUCKETS}
        self.steps = 0
        self.failures = 0
        # Elastic membership changes (not failures): count + last transition.
        self.resizes = 0
        self.last_resize: Optional[Dict[str, Any]] = None
        self.proactive_checkpoints = 0
        self.status = "running"
        self.max_skew_s = 0.0
        self.last_skew_s = 0.0
        self.per_rank: Dict[str, Dict[str, Any]] = {}
        # Straggler naming is modal, not max: the rank that is slowest in the
        # most rounds. A single noisy round (gang bring-up stagger inflates
        # everyone's first step differently) must not name the straggler for
        # the whole run.
        self._slow_rounds: Dict[int, int] = {}
        self._slow_last: Dict[int, Dict[str, Any]] = {}
        self._last_publish = 0.0
        # Bring-up: the driver's span log (its root is opened by the trainer,
        # one an attempt) and the finished spans of driver and ranks.
        from ray_tpu._private.config import get_config
        from ray_tpu.util import tracing

        self.spans = SpanLog(
            gang, push=bool(get_config().enable_timeline) or tracing.is_enabled())
        self.bringup: List[Dict[str, Any]] = []
        # What the report holds of them, as data and encoded, and how many it left out.
        self._bringup_out: List[Dict[str, Any]] = []
        self._bringup_json = "[]"
        self._bringup_cut = 0
        # rank -> that process's compile counter as its session last sent it.
        self.compile_by_rank: Dict[int, Dict[str, Any]] = {}

    # ------------------------------------------------------------- intervals
    def _take(self) -> float:
        now = time.perf_counter()
        dt = max(0.0, now - self._mark)
        self._mark = now
        return dt

    def account(self, bucket: str) -> float:
        """Classify everything since the last mark into one bucket."""
        dt = self._take()
        self.buckets[bucket] += dt
        return dt

    def note_spans(self, spans: List[Dict[str, Any]]) -> None:
        """Fold in finished bring-up spans: a worker's, from a reply, or the
        driver's own log."""
        self.bringup.extend(spans)

    def open_root(self, attempt: int) -> Dict[str, Any]:
        """The root of one attempt's bring-up; every span of it, in every
        process, shares its trace id."""
        self.spans.context = None
        root = self.spans.open(ROOT_SPAN, world_size=self.world_size, attempt=attempt)
        self.spans.context = {"trace_id": root["trace_id"], "parent_id": root["span_id"]}
        return root

    def close_root(self, root: Dict[str, Any], status: str = "OK") -> None:
        self.spans.close(root, status)
        self.note_spans(self.spans.take())

    def account_init(self) -> None:
        """First bring-up window: the gang-join blocking the workers measured
        (their `distributed_init` spans, gang mean) is rendezvous_wait; the
        rest (PG, actor spawn, backend) is init."""
        self.note_spans(self.spans.take())
        joins = [span_seconds(s) for s in self.bringup
                 if s["name"] == DISTRIBUTED_INIT_SPAN]
        rendezvous_s = sum(joins) / len(joins) if joins else 0.0
        dt = self._take()
        r = min(max(0.0, rendezvous_s), dt)
        self.buckets["rendezvous_wait"] += r
        self.buckets["init"] += dt - r
        self.publish()

    def fold_round(self, telems: List[Dict[str, Any]]) -> None:
        """Classify one result round from the gang's per-step telemetry dicts
        (one per rank; may be empty when observability is off)."""
        dt = self._take()
        if not telems:
            self.buckets["idle"] += dt
            return
        self.steps += 1
        n = len(telems)
        means: Dict[str, float] = {}
        for t in telems:
            if "spans" in t:  # a rank's first report carries its bring-up
                self.note_spans(t["spans"])
            for p, v in (t.get("phases") or {}).items():
                means[p] = means.get(p, 0.0) + v / n
        total = sum(means.values())
        # Worker clocks can drift past the driver-observed interval; scale
        # down so the round never claims more wall time than it occupied.
        scale = min(1.0, dt / total) if total > 0.0 else 0.0
        for p, v in means.items():
            self.buckets[_PHASE_BUCKET.get(p, "idle")] += v * scale
        self.buckets["idle"] += dt - total * scale
        self.publish()

    def note_skew(self, skew_s: float, straggler: Optional[Dict[str, Any]],
                  per_rank: Dict[str, Dict[str, Any]]) -> None:
        self.last_skew_s = skew_s
        self.max_skew_s = max(self.max_skew_s, skew_s)
        if straggler is not None:
            rank = straggler["rank"]
            self._slow_rounds[rank] = self._slow_rounds.get(rank, 0) + 1
            self._slow_last[rank] = straggler
        self.per_rank = per_rank

    def note_resize(self, old_world: int, new_world: int, reason: str,
                    resize_s: float, ckpt_source: str) -> None:
        """Record one elastic membership change; the wall time was already
        accounted into the resize bucket by the trainer."""
        self.resizes += 1
        self.world_size = new_world
        self.last_resize = {
            "old_world": old_world,
            "new_world": new_world,
            "direction": "grow" if new_world > old_world else "shrink",
            "reason": reason,
            "resize_s": round(resize_s, 6),
            "ckpt_source": ckpt_source,
        }
        self.publish(force=True)

    @property
    def straggler(self) -> Optional[Dict[str, Any]]:
        """The modal slow rank with its latest round's phase attribution,
        plus how many rounds it was the slowest."""
        if not self._slow_rounds:
            return None
        rank = max(self._slow_rounds, key=self._slow_rounds.get)
        out = dict(self._slow_last[rank])
        out["slow_rounds"] = self._slow_rounds[rank]
        out["rounds"] = sum(self._slow_rounds.values())
        return out

    def note_totals(self, rank: int, totals: Dict[str, Any]) -> None:
        """A rank's cumulative telemetry, from its session's last result."""
        if "spans" in totals:
            self.note_spans(totals["spans"])
        if "compile" in totals:
            self.compile_by_rank[rank] = totals["compile"]

    # --------------------------------------------------------------- report
    def wall_s(self) -> float:
        return time.perf_counter() - self._wall_t0

    def _bringup_report(self) -> List[Dict[str, Any]]:
        """The finished spans as the report holds them. Built anew only when
        one more has finished: a round's publish must not pay for bring-up."""
        spans = self.bringup + [s for s in self.spans.spans if s.get("end")]
        if len(spans) != len(self._bringup_out) + self._bringup_cut:
            self._bringup_cut = max(0, len(spans) - MAX_BRINGUP_SPANS)
            if self._bringup_cut:
                spans = sorted(spans, key=lambda s: s["attributes"].get("rank", -1))
                spans = spans[:MAX_BRINGUP_SPANS]
            keep = ("name", "kind", "trace_id", "span_id", "parent_id", "start", "end",
                    "status", "pid", "attributes")
            self._bringup_out = [{k: s.get(k) for k in keep}
                                 for s in sorted(spans, key=lambda s: s["start"])]
            self._bringup_json = json.dumps(self._bringup_out)
        return self._bringup_out

    def _compile_report(self) -> Dict[str, Any]:
        if not self.compile_by_rank:
            return {}
        rank0 = self.compile_by_rank.get(0) or {}
        totals = [k for k in rank0 if k != "functions"]
        return {
            "rank0": rank0,
            "gang_max": {k: max(c.get(k, 0) for c in self.compile_by_rank.values())
                         for k in totals},
        }

    def report(self) -> Dict[str, Any]:
        wall = self.wall_s()
        accounted = sum(self.buckets.values())
        return {
            "gang": self.gang,
            "world_size": self.world_size,
            "status": self.status,
            "updated_at": time.time(),
            "wall_s": round(wall, 6),
            "buckets": {b: round(v, 6) for b, v in self.buckets.items()},
            "coverage": round(accounted / wall, 4) if wall > 0 else 1.0,
            "goodput_frac": round(self.buckets["productive"] / wall, 4)
            if wall > 0 else 0.0,
            "steps": self.steps,
            "failures": self.failures,
            "resizes": self.resizes,
            "last_resize": self.last_resize,
            "proactive_checkpoints": self.proactive_checkpoints,
            "skew_s": round(self.last_skew_s, 6),
            "max_skew_s": round(self.max_skew_s, 6),
            "straggler": self.straggler,
            "per_rank": self.per_rank,
            "bringup": self._bringup_report(),
            "compile": self._compile_report(),
        }

    def publish(self, force: bool = False) -> None:
        """Best-effort KV write of the current report (throttled mid-run).
        Gated on the observability knob; never raises."""
        try:
            from ray_tpu._private.telemetry import obs_enabled

            if not obs_enabled():
                return
            now = time.monotonic()
            if not force and now - self._last_publish < _PUBLISH_INTERVAL_S:
                return
            self._last_publish = now
            from ray_tpu._private.worker import global_worker

            ctx = global_worker.context
            if ctx is None:
                return
            # The spans are encoded once, not once a round.
            report = self.report()
            del report["bringup"]
            payload = f'{json.dumps(report)[:-1]}, "bringup": {self._bringup_json}}}'
            ctx.kv("put", report_key(self.gang), payload.encode())
        except Exception:  # noqa: BLE001 — shutdown races, head gone
            pass

    def finalize(self, status: str) -> Dict[str, Any]:
        """Sweep the tail into idle, stamp final status, publish."""
        self.account("idle")
        self.status = status
        self.publish(force=True)
        report = self.report()
        _KEPT[self.gang] = report
        _KEPT.move_to_end(self.gang)
        while len(_KEPT) > _KEPT_GANGS:
            _KEPT.popitem(last=False)
        return report
