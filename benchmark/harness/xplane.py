"""From a jax profiler trace (`.xplane.pb`) to the numbers the per-layer
metrics read. The yardstick: every PR reduces its trace with this file.

What a v5e trace holds (looked at by hand, PR 22): one plane per chip,
`/device:TPU:<n>`, with the lines `XLA Modules` (one event per execution of a
compiled program, stat `run_id`) and `XLA Ops` (one event per HLO instruction
executed, named by the instruction's full text; a `while` spans the ops of its
body, and asynchronous copies overlap compute, so durations do not add up to
the module's: busy time is a union). `Async XLA Ops` holds start-to-done
spans. `/host:CPU` has one line per thread; `TraceAnnotation`s appear on the
line of the thread that opened them under their own names. The device clock
lags the host's by a millisecond or two; `DoEnqueueProgram` and
`CompleteCallbacks` on the host carry the same `run_id` as the module events
and bound the offset from both sides.

`extract` (needs jax, runs in the worker that traced) turns the file into a
JSON-able table; everything else is plain arithmetic on that table and runs
anywhere.
"""

from __future__ import annotations

import re
from statistics import median
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]

# Instructions whose event spans their children's: counting them would count
# the children twice.
CONTROL_FLOW = ("while", "conditional", "call")
COLLECTIVES = ("all-gather", "reduce-scatter", "all-reduce", "collective-permute", "all-to-all")
MOSAIC_TARGET = "tpu_custom_call"
ANNOTATION_PREFIX = "bench."
STEP_ANNOTATION = "bench.step"


# --------------------------------------------------------------- intervals
def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[List[float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def measure(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in union(intervals))


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)]


def subtract(a: Iterable[Interval], b: Iterable[Interval]) -> List[Interval]:
    """The part of `a` that no interval of `b` covers."""
    out: List[Interval] = []
    cover = union(b)
    for lo, hi in union(a):
        at = lo
        for c, d in cover:
            if d <= at:
                continue
            if c >= hi:
                break
            if c > at:
                out.append((at, c))
            at = max(at, d)
        if at < hi:
            out.append((at, hi))
    return out


# ------------------------------------------------------------- instruction text
def parse_op(text: str) -> Tuple[str, str, str]:
    """(name, opcode, custom-call target) of an `XLA Ops` event name such as
    `%fusion.3 = bf16[8,128]{1,0} fusion(...), kind=kLoop` or, for a Mosaic
    kernel, `%closed_call.28 = (bf16[..], f32[..]) custom-call(...),
    custom_call_target="tpu_custom_call"`."""
    m = re.match(r"%?(\S+) = ", text)
    if not m:
        return text.split(" ")[0].lstrip("%"), "", ""
    name, rest = m.group(1), text[m.end():]
    if rest.startswith("("):  # a tuple type: skip to its closing bracket
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:].lstrip()
                break
    else:
        rest = rest.partition(" ")[2]
    opcode = re.match(r"[a-z][a-z0-9\-]*", rest)
    target = re.search(r'custom_call_target="([^"]+)"', text)
    return name, opcode.group(0) if opcode else "", target.group(1) if target else ""


def result_type(text: str) -> str:
    """The first array type of the instruction's result, for a readable label."""
    m = re.search(r"= \(?([a-z0-9]+\[[0-9,]*\])", text)
    return m.group(1) if m else ""


def is_collective(opcode: str) -> bool:
    return opcode.startswith(COLLECTIVES)


# ------------------------------------------------------------------- extract
def extract(path: str) -> Dict:
    """The table of one `.xplane.pb`. Times are nanoseconds from the start of
    the trace, as the profiler gives them.

    {"devices": [{"name", "modules": [[name, run_id, start, dur]],
                  "ops": [[name, opcode, target, type, start, dur]],
                  "async": [[name, opcode, start, dur]]}],
     "annotations": [[name, start, dur, step_num or -1]],
     "enqueued": {run_id: host start}, "completed": {run_id: host start}}
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    table: Dict = {"devices": [], "annotations": [], "enqueued": {}, "completed": {}}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"name": plane.name, "modules": [], "ops": [], "async": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for ev in line.events:
                        stats = dict(ev.stats)
                        dev["modules"].append(
                            [ev.name.split("(")[0], int(stats.get("run_id", -1)),
                             ev.start_ns, ev.duration_ns])
                elif line.name in ("XLA Ops", "Async XLA Ops"):
                    for ev in line.events:
                        name, opcode, target = parse_op(ev.name)
                        if line.name == "XLA Ops":
                            dev["ops"].append([name, opcode, target, result_type(ev.name),
                                               ev.start_ns, ev.duration_ns])
                        else:
                            dev["async"].append([name, opcode, ev.start_ns, ev.duration_ns])
            table["devices"].append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(ANNOTATION_PREFIX):
                        stats = dict(ev.stats)
                        table["annotations"].append(
                            [ev.name, ev.start_ns, ev.duration_ns, int(stats.get("step_num", -1))])
                    elif ev.name in ("DoEnqueueProgram", "CompleteCallbacks"):
                        stats = dict(ev.stats)
                        key = "enqueued" if ev.name == "DoEnqueueProgram" else "completed"
                        if "run_id" in stats:
                            table[key][str(int(stats["run_id"]))] = ev.start_ns
    table["devices"].sort(key=lambda d: d["name"])
    table["annotations"].sort(key=lambda a: a[1])
    return table


# -------------------------------------------------------------------- reduce
class Trace:
    """One worker's trace table with the reductions the metrics share. Device
    times are moved onto the host's clock once, here."""

    def __init__(self, table: Dict):
        self.table = table
        self.offset_ns = self._host_minus_device_ns()
        self.devices = []
        for dev in table["devices"]:
            off = self.offset_ns
            self.devices.append({
                "name": dev["name"],
                "modules": [[n, r, s + off, d] for n, r, s, d in dev["modules"]],
                "ops": [[n, o, t, ty, s + off, d] for n, o, t, ty, s, d in dev["ops"]],
                "async": [[n, o, s + off, d] for n, o, s, d in dev["async"]],
            })
        self.annotations = table["annotations"]
        steps = [a for a in self.annotations if a[0] == STEP_ANNOTATION]
        # The traced window on the host's clock: first traced step's start to
        # the last one's end.
        self.window: Interval = (
            (steps[0][1], max(a[1] + a[2] for a in steps)) if steps else (0.0, 0.0))
        self.host_steps = len(steps)

    def _host_minus_device_ns(self) -> float:
        """A program cannot start before the host enqueued it nor end after
        the host saw it complete: the device clock is the host's minus
        something in [max(enqueue - start), min(complete - end)]. The middle
        of that range, or 0 where the events are missing."""
        lo, hi = [], []
        for dev in self.table["devices"]:
            for _, run_id, start, dur in dev["modules"]:
                rid = str(run_id)
                if rid in self.table["completed"]:
                    hi.append(self.table["completed"][rid] - (start + dur))
                if rid in self.table["enqueued"]:
                    lo.append(self.table["enqueued"][rid] - start)
        if not hi:
            return 0.0
        return (min(hi) + max(lo)) / 2 if lo and max(lo) <= min(hi) else min(hi)

    # ---- device busy / idle
    def _leaf_ops(self, dev) -> List[List]:
        return [op for op in dev["ops"] if op[1] not in CONTROL_FLOW]

    def busy(self, dev, lo: Optional[float] = None, hi: Optional[float] = None) -> List[Interval]:
        lo = self.window[0] if lo is None else lo
        hi = self.window[1] if hi is None else hi
        return union(clip(((op[4], op[4] + op[5]) for op in self._leaf_ops(dev)), lo, hi))

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over this trace's devices."""
        if not self.devices:
            return 0.0
        return sum(measure(self.busy(d)) for d in self.devices) / len(self.devices) / 1e9

    def idle_gaps(self, dev) -> List[Interval]:
        return subtract([self.window], self.busy(dev))

    def attribute(self, gap: Interval) -> str:
        """The benchmark's host span (not the step's own) that covers most of
        the gap, or `unattributed`."""
        best, best_overlap = "unattributed", 0.0
        for name, start, dur, _ in self.annotations:
            if name == STEP_ANNOTATION:
                continue
            overlap = min(gap[1], start + dur) - max(gap[0], start)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        return best

    def idle_by_host_span(self) -> List[Tuple[str, float]]:
        """Idle seconds of the first device by what the host was doing, most first."""
        if not self.devices:
            return []
        total: Dict[str, float] = {}
        for gap in self.idle_gaps(self.devices[0]):
            name = self.attribute(gap)
            total[name] = total.get(name, 0.0) + (gap[1] - gap[0]) / 1e9
        return sorted(total.items(), key=lambda kv: -kv[1])

    # ---- steps
    def step_runs(self, dev) -> List[List]:
        """Executions of the step's program inside the window: the module with
        the most device time is the step."""
        lo, hi = self.window
        inside = [m for m in dev["modules"] if lo <= m[2] and m[2] + m[3] <= hi]
        by_name: Dict[str, float] = {}
        for name, _, _, dur in inside:
            by_name[name] = by_name.get(name, 0.0) + dur
        if not by_name:
            return []
        step = max(by_name, key=by_name.get)
        return [m for m in inside if m[0] == step]

    def per_step(self, dev, pick) -> List[float]:
        """For each step run, the nanoseconds covered by the ops `pick` keeps."""
        out = []
        for _, _, start, dur in self.step_runs(dev):
            end = start + dur
            out.append(measure(clip(
                ((op[4], op[4] + op[5]) for op in self._leaf_ops(dev) if pick(op)), start, end)))
        return out

    def step_device_ms(self) -> Optional[float]:
        runs = self.per_step(self.devices[0], lambda op: True) if self.devices else []
        return median(runs) / 1e6 if runs else None

    def mosaic_ms(self) -> Optional[float]:
        """Median over steps of the device time of the Mosaic calls."""
        runs = self.per_step(self.devices[0], lambda op: op[2] == MOSAIC_TARGET) if self.devices else []
        return median(runs) / 1e6 if runs else None

    # ---- collectives
    def _collective_intervals(self, dev) -> List[Interval]:
        sync = [(op[4], op[4] + op[5]) for op in dev["ops"]
                if is_collective(op[1]) and not op[1].endswith(("-start", "-done"))]
        spans = [(s, s + d) for _, opcode, s, d in dev["async"] if is_collective(opcode)]
        return union(sync + spans)

    def collectives_ms(self) -> Optional[Tuple[float, float]]:
        """(total, exposed) milliseconds per step on the first device: time in
        which a collective was in flight, and the part of it in which no other
        operation ran."""
        if not self.devices:
            return None
        dev = self.devices[0]
        others = union((op[4], op[4] + op[5]) for op in self._leaf_ops(dev)
                       if not is_collective(op[1]))
        coll = self._collective_intervals(dev)
        totals, exposed = [], []
        for _, _, start, dur in self.step_runs(dev):
            mine = clip(coll, start, start + dur)
            totals.append(measure(mine))
            exposed.append(measure(subtract(mine, others)))
        if not totals:
            return None
        return median(totals) / 1e6, median(exposed) / 1e6

    # ---- breakdown
    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """Device operations with the most time in the window (first device)."""
        if not self.devices:
            return []
        lo, hi = self.window
        total: Dict[str, float] = {}
        for name, opcode, target, rtype, start, dur in self._leaf_ops(self.devices[0]):
            if lo <= start < hi:
                label = " ".join(x for x in (name, target or opcode, rtype) if x)
                total[label] = total.get(label, 0.0) + dur / 1e9
        return sorted(total.items(), key=lambda kv: -kv[1])[:n]
