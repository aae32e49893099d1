"""The program's own account of a run's set-up: the bring-up spans and the
compile counter in the goodput ledger's final report, which the process that
ran `fit()` keeps (`ray_tpu.train._internal.ledger.kept_reports`), so it is
there after `driver.run` shut the cluster down. `of(run)` finds the report of
the run's one `fit()`, prints the two `[run]` lines only this file can write
and leaves them in the run's summary, as `program_trace.of` does for the
device's side. Nothing where the process kept no report: a recorded trace, or
a program from before the spans."""

from __future__ import annotations

import json
from typing import Any, Dict, List, Optional, Tuple

ROOT = "ray_tpu.train.fit"
BRINGUP = "ray_tpu.train.bringup."
WORKER = "ray_tpu.train.worker."
CHIP_OPEN = "device_touch"  # the worker span round the first `jax.local_devices()`: libtpu opens the chip
CHIP_OPEN_SPAN = WORKER + CHIP_OPEN
TOP_FUNCTIONS = 5


def _seconds(span: Dict[str, Any]) -> float:
    return max(0.0, (span.get("end") or span["start"]) - span["start"])


def _union(intervals: List[Tuple[float, float]]) -> float:
    total, reach = 0.0, float("-inf")
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        total += max(0.0, hi - max(lo, reach))
        reach = max(reach, hi)
    return total


class Bringup:
    """One `fit()`'s bring-up spans and rank 0's compile counter, beside the
    benchmark's own two marks round them (`t_fit_wall`, `t_loop_wall`: the
    same clock, `time.time()`)."""

    def __init__(self, report: Dict[str, Any], run: Dict[str, Any]):
        roots = [s for s in report["bringup"] if s["name"] == ROOT]
        self.root = roots[-1]  # the attempt that ran to the end
        self.spans = [s for s in report["bringup"] if s["trace_id"] == self.root["trace_id"]]
        self.compile: Dict[str, Any] = (report.get("compile") or {}).get("rank0") or {}
        self.t_fit = run["parent"]["t_fit_wall"]
        self.t_loop = run["summary"]["t_loop_wall"]

    # ---- spans
    def named(self, name: str) -> List[Dict[str, Any]]:
        return [s for s in self.spans if s["name"] == name]

    def driver_s(self, name: str) -> float:
        """Seconds of a driver's seam (all of its spans: one an attempt)."""
        return sum(_seconds(s) for s in self.named(BRINGUP + name))

    def by_rank(self, *names: str) -> Dict[int, float]:
        """rank -> seconds of the named worker spans together."""
        out: Dict[int, float] = {}
        for name in names:
            for s in self.named(WORKER + name):
                rank = s["attributes"].get("rank", 0)
                out[rank] = out.get(rank, 0.0) + _seconds(s)
        return out

    def slowest(self, *names: str) -> Optional[float]:
        ranks = self.by_rank(*names)
        return max(ranks.values()) if ranks else None

    @property
    def train_fn_entered(self) -> Optional[float]:
        """Rank 0 entered `train_fn`: its `first_report` span begins."""
        starts = [s["start"] for s in self.named(WORKER + "first_report")
                  if s["attributes"].get("rank", 0) == 0]
        return starts[0] if starts else None

    # ---- the six
    @property
    def spawn_s(self) -> float:
        return self.driver_s("placement") + self.driver_s("spawn")

    @property
    def backend_start_s(self) -> float:
        return self.driver_s("backend")

    @property
    def device_touch_s(self) -> Optional[float]:
        return self.slowest("import_jax", "device_touch")

    @property
    def chip_open_s(self) -> Optional[float]:
        """The slowest rank's `device_touch` alone: libtpu opening the chip,
        which `setup_s` leaves out (`driver.set_up`). Nothing where no rank
        opened the span: a lone CPU worker."""
        return self.slowest(CHIP_OPEN)

    @property
    def gang_join_s(self) -> Optional[float]:
        return self.slowest("distributed_init")

    @property
    def session_start_s(self) -> Optional[float]:
        session = self.named(BRINGUP + "session")
        entered = self.train_fn_entered
        return entered - session[-1]["start"] if session and entered is not None else None

    @property
    def unaccounted_s(self) -> Optional[float]:
        """`fit()` -> loop entered less what the four seams above cover."""
        entered = self.train_fn_entered
        session = self.named(BRINGUP + "session")
        if entered is None or not session:
            return None
        seams = [(s["start"], s["end"]) for name in ("placement", "spawn", "backend")
                 for s in self.named(BRINGUP + name)]
        seams.append((session[-1]["start"], entered))
        clipped = [(max(lo, self.t_fit), min(hi, self.t_loop)) for lo, hi in seams]
        return (self.t_loop - self.t_fit) - _union(clipped)

    # ---- what the lines say
    def timeline(self) -> Dict[str, List[float]]:
        """Short name -> [rank 0's seconds, the slowest rank's]; a driver's
        seam twice."""
        out: Dict[str, List[float]] = {}
        for s in self.spans:
            name = s["name"]
            if name == ROOT or name in out:
                continue
            if name.startswith(WORKER) or "rank" in s["attributes"]:
                ranks: Dict[int, float] = {}
                for t in self.named(name):
                    rank = t["attributes"].get("rank", 0)
                    ranks[rank] = ranks.get(rank, 0.0) + _seconds(t)
                out[name] = [ranks.get(0, 0.0), max(ranks.values())]
            else:
                out[name] = [sum(_seconds(t) for t in self.named(name))] * 2
        return {k.replace(BRINGUP, "").replace(WORKER, "worker."): [round(v, 3) for v in pair]
                for k, pair in out.items()}

    def top_functions(self) -> List[List[Any]]:
        """Rank 0's functions by trace + lowering seconds: [name, those
        seconds, traces, lowerings, backend seconds]."""
        rows = sorted(self.compile.get("functions", {}).items(),
                      key=lambda kv: -(kv[1]["trace_s"] + kv[1]["lower_s"]))
        return [[n, round(r["trace_s"] + r["lower_s"], 3), r["traces"], r["lowerings"],
                 round(r["backend_s"], 3)] for n, r in rows[:TOP_FUNCTIONS]]


def kept_report(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The final report of the `fit()` this run made, from the reports this
    process keeps: the first whose root span opened after `t_fit_wall`."""
    t_fit = (run.get("parent") or {}).get("t_fit_wall")
    if t_fit is None:
        return None
    try:
        from ray_tpu.train._internal import ledger
    except ImportError:
        return None
    for report in getattr(ledger, "kept_reports", list)():
        roots = [s for s in report.get("bringup") or [] if s["name"] == ROOT]
        if roots and roots[0]["start"] >= t_fit:
            return report
    return None


def of(run: Dict[str, Any]) -> Optional[Bringup]:
    """The run's `Bringup`, or nothing; kept on `run`, which
    `driver.result_line` hands to every reader in turn."""
    if "bringup" in run:
        return run["bringup"]
    report = kept_report(run)
    bringup = run["bringup"] = Bringup(report, run) if report is not None else None
    if bringup is None:
        return None
    s = run["summary"]
    entered = bringup.train_fn_entered
    timeline = bringup.timeline()
    totals = {k: v for k, v in bringup.compile.items() if k != "functions"}
    s["bringup"] = {"timeline_s": timeline, "unaccounted_s": bringup.unaccounted_s}
    s["compile_counter"] = {"totals": totals, "top_functions": bringup.top_functions(),
                            "functions": bringup.compile.get("functions", {})}
    print(f"[run] bring-up s by span [rank 0, slowest rank] {json.dumps(timeline)}; fit() -> "
          f"train_fn entered {(entered or bringup.t_fit) - bringup.t_fit:.3f}s, -> loop entered "
          f"{bringup.t_loop - bringup.t_fit:.3f}s, of which no seam covers "
          f"{bringup.unaccounted_s if bringup.unaccounted_s is not None else float('nan'):.3f}s")
    heard = s.get("compiles_setup", {}).get("seconds", 0.0) + s.get(
        "compiles_in_window", {}).get("seconds", 0.0)
    print(f"[run] compile counter, rank 0 over the fit() {json.dumps(totals)}; top functions by "
          f"trace + lowering s [name, s, traces, lowerings, backend s] "
          f"{json.dumps(bringup.top_functions())}; backend_s against the benchmark's own "
          f"listener (set-up + window) {heard:.6f}", flush=True)
    return bringup
