"""What the program says about its own work, beside what the device did.

`xplane.py` reduces a trace by what XLA calls things. This file reads what
`ray_tpu` itself named: the `jax.named_scope`s of the train step (`embed`,
`blocks`, `qkv`, `attention`, `out_mlp`, `head`, `loss`, `optimizer`,
`grad_norm`), the kernels' `name=` (`flash_fwd`, `flash_bwd`), and the
`ray_tpu.*` annotations at the framework's seams (`ray_tpu.util.tracing.annotate`).

Where the names are (looked at by hand, PR 24, jax 0.9.0): every instruction
of a compiled program carries `metadata={op_name="jit(step_fn)/transpose(
jvp(blocks))/while/body/closed_call/qkv/qkv/checkpoint/rematted_computation/
dot_general"}`, fusions by their root. `compiled.as_text()` shows it
(`scope_map`), and so does the trace itself: a v5e `.xplane.pb` keeps, for
every instruction that ran, an event metadata entry whose display name is the
instruction's name and whose stat `tf_op` is `<op_name>:<op_type>`. jax's
`ProfileData` does not hand out event metadata, so `read_xplane` walks the
protobuf's wire format itself (no jax, no tensorflow: the parent of a run
imports neither). The annotations are events of `/host:CPU` under their own
names with their keyword arguments as stats, on the profiler's clock, the
same one `xplane.extract` reads `bench.*` from.

Plain arithmetic on the table `xplane.extract` made and on the raw trace
beside it; nothing here needs the chip.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import struct
from statistics import median
from typing import Any, Dict, Iterator, List, Optional, Tuple

from benchmark.harness import xplane

# The order decides who gets a nanosecond that two phases cover at once (an
# asynchronous copy without a name under a matmul with one): the first.
PHASES = ("forward", "recompute", "backward", "optimizer", "other")
PROGRAM_PREFIX = "ray_tpu."
FLASH_FWD, FLASH_BWD = "flash_fwd", "flash_bwd"
FLASH_PREFIX = "flash_"  # every kernel of `ops/flash_attention.py` is named so
UNNAMED_KERNEL = "closed_call"  # what stands before `pallas_call` where the kernel was given no name


# ------------------------------------------------------------------- scopes
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')


def scope_map(hlo_text: str) -> Dict[str, str]:
    """{instruction: op_name} of a compiled program's text, for every
    instruction that has one and can run: what the compiler made up has none,
    and a `parameter` (named after the argument or the reducer it belongs to)
    is never an operation in a trace."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and " parameter(" not in line:
            out[m.group(1)] = m.group(2)
    return out


def phase(op_name: str) -> str:
    """The part of the train step an `op_name` belongs to. `jax.value_and_grad`
    puts the first pass under `jvp(<scope>)` and the second under
    `transpose(jvp(<scope>))`; what `jax.checkpoint` runs again there sits
    under `rematted_computation`; the update and the gradient norm are not
    differentiated and carry their scope bare."""
    parts = op_name.split("/")
    if "optimizer" in parts or "grad_norm" in parts:
        return "optimizer"
    if "rematted_computation" in parts:
        return "recompute"
    if "transpose(" in op_name:
        return "backward"
    if "jvp(" in op_name:
        return "forward"
    return "other"


def names_the_step(scopes: Dict[str, str]) -> bool:
    """Whether the program behind `scopes` carries this file's names at all
    (a parent of PR 24 has `jvp(` and `transpose(` but no `optimizer`)."""
    return any("optimizer" in name.split("/") for name in scopes.values())


# ----------------------------------------------------------- the raw trace
def _fields(buf) -> Iterator[Tuple[int, int, Any]]:
    """(field number, wire type, value) of one protobuf message: varints as
    int, length-delimited fields as a slice of `buf`, fixed ones as bytes."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                break
        wire = key & 7
        if wire == 0 or wire == 2:
            value = shift = 0
            while True:
                b = buf[i]
                i += 1
                value |= (b & 0x7F) << shift
                shift += 7
                if b < 0x80:
                    break
            if wire == 2:
                value, i = buf[i:i + value], i + value
        elif wire == 1:
            value, i = bytes(buf[i:i + 8]), i + 8
        elif wire == 5:
            value, i = bytes(buf[i:i + 4]), i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane")
        yield key >> 3, wire, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _stat(buf, stat_names: Dict[int, str]) -> Tuple[str, Any]:
    """One XStat: metadata_id=1, double=2, uint64=3, int64=4, str=5, bytes=6, ref=7."""
    name, value = "", None
    for f, _, v in _fields(buf):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = _signed(v)
        elif f == 5:
            value = _text(v)
        elif f == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _plane(buf):
    """name, lines, event metadata and stat names of one XPlane (name=2,
    lines=3, event_metadata=4 and stat_metadata=5 as maps of id -> message)."""
    name, lines, events, stat_names = "", [], {}, {}
    for f, _, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            lines.append(v)
        elif f in (4, 5):
            entry = {k: x for k, _, x in _fields(v)}
            if f == 4:
                events[entry[1]] = entry[2]
            else:  # XStatMetadata: id=1, name=2
                stat_names[entry[1]] = next(
                    (_text(x) for k, _, x in _fields(entry[2]) if k == 2), "")
    return name, lines, events, stat_names


def _device_scopes(events, stat_names) -> Dict[Any, Dict[str, str]]:
    """{program id: {instruction: op_name}} of a device plane's event metadata
    (XEventMetadata: name=2 is the instruction's text, display_name=4 its
    name, stats=5 hold `tf_op` = `<op_name>:<op_type>` and `program_id`)."""
    programs: Dict[Any, Dict[str, str]] = {}
    for meta in events.values():
        display, stats = "", {}
        for k, _, v in _fields(meta):
            if k == 4:
                display = _text(v)
            elif k == 5:
                key, value = _stat(v, stat_names)
                stats[key] = value
        if display and stats.get("tf_op"):
            op_name = stats["tf_op"].rpartition(":")[0] or stats["tf_op"]
            programs.setdefault(stats.get("program_id"), {})[display] = op_name
    return programs


def _host_spans(lines, events, stat_names) -> List[List]:
    """[name, start_ns, dur_ns, stats] of the host plane's `ray_tpu.*` events
    (XLine: timestamp_ns=3, events=4; XEvent: metadata_id=1, offset_ps=2,
    duration_ps=3, stats=4)."""
    wanted = {}
    for key, meta in events.items():
        label = next((_text(v) for k, _, v in _fields(meta) if k == 2), "")
        if label.startswith(PROGRAM_PREFIX):
            wanted[key] = label
    spans: List[List] = []
    for line in lines if wanted else ():
        t0, events_of_line = 0, []
        for k, _, v in _fields(line):
            if k == 3:
                t0 = v
            elif k == 4:
                events_of_line.append(v)
        for ev in events_of_line:
            label, offset, dur, stats = None, 0, 0, {}
            for k, _, v in _fields(ev):
                if k == 1:
                    label = wanted.get(v)
                    if label is None:
                        break
                elif k == 2:
                    offset = v
                elif k == 3:
                    dur = v
                elif k == 4:
                    key, value = _stat(v, stat_names)
                    stats[key] = value
            if label is not None:
                spans.append([label, t0 + offset / 1000.0, dur / 1000.0, stats])
    return spans


def read_xplane(path: str) -> Dict[str, Any]:
    """{"scopes": {instruction: op_name} of the program with the most
    instructions in the trace (the step; a gang's vote has a handful, and its
    `fusion.1` must not name the step's), "program_spans": [[name, start_ns,
    dur_ns, {stat: value}]] of the host's `ray_tpu.*` events, by start}.
    Times as `xplane.extract` gives them. `path` may be gzipped."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as fh:
        space = memoryview(fh.read())
    programs: Dict[Any, Dict[str, str]] = {}
    spans: List[List] = []
    for f, _, plane in _fields(space):
        if f != 1:
            continue
        name, lines, events, stat_names = _plane(plane)
        if name.startswith("/device:TPU:"):
            for program, scopes in _device_scopes(events, stat_names).items():
                programs.setdefault(program, {}).update(scopes)
        elif name == "/host:CPU":
            spans += _host_spans(lines, events, stat_names)
    step = max(programs.values(), key=len, default={})
    return {"scopes": step, "program_spans": sorted(spans, key=lambda s: s[1])}


# ------------------------------------------------------------------- reduce
def phase_ms(trace: xplane.Trace, scopes: Dict[str, str]) -> Optional[Dict[str, float]]:
    """{phase: ms per step}: inside each run of the step's program on the
    first device, the busy union (the `_leaf_ops`, `union` and `clip` of
    `Trace.per_step`) of each phase's operations, less what an earlier phase
    of `PHASES` already covers, so that the five add up to the step's busy
    time; the median over the traced steps."""
    if not trace.devices:
        return None
    dev = trace.devices[0]
    by_phase: Dict[str, List[xplane.Interval]] = {p: [] for p in PHASES}
    for op in trace._leaf_ops(dev):
        by_phase[phase(scopes.get(op[0], ""))].append((op[4], op[4] + op[5]))
    per_step: Dict[str, List[float]] = {p: [] for p in PHASES}
    for _, _, start, dur in trace.step_runs(dev):
        covered: List[xplane.Interval] = []
        for p in PHASES:
            mine = xplane.union(xplane.clip(by_phase[p], start, start + dur))
            per_step[p].append(xplane.measure(xplane.subtract(mine, covered)))
            covered = xplane.union(covered + mine)
    if not per_step["other"]:
        return None
    return {p: median(v) / 1e6 for p, v in per_step.items()}


def kernel_ms(trace: xplane.Trace, scopes: Dict[str, str], kernel: str) -> Optional[float]:
    """Median over the traced steps of the device time of the Mosaic calls
    whose scope holds `kernel` (`pl.pallas_call(name=...)`)."""
    if not trace.devices:
        return None
    runs = trace.per_step(trace.devices[0], lambda op: (
        op[2] == xplane.MOSAIC_TARGET and kernel in scopes.get(op[0], "").split("/")))
    return median(runs) / 1e6 if runs and max(runs) > 0 else None


def flash_ms(trace: xplane.Trace, scopes: Dict[str, str]) -> Optional[float]:
    """Median over the traced steps of the device time of the flash kernels:
    the Mosaic calls whose scope has a component that starts with `flash_`,
    summed inside each step before the median is taken; where every Mosaic
    call is one, that is `Trace.mosaic_ms()` to the last bit. A Mosaic kernel
    of another name (`pl.pallas_call(name=...)`, the component before
    `pallas_call`) is its own reader's, and where kernels carry names and none
    is a flash kernel there is nothing to read. Only where no Mosaic call
    carries a name (a tree before PR 24: `closed_call/pallas_call`) does every
    one count."""
    if not trace.devices:
        return None
    dev = trace.devices[0]
    parts = {op[0]: scopes.get(op[0], "").split("/")
             for op in dev["ops"] if op[2] == xplane.MOSAIC_TARGET}
    flash = {name for name, path in parts.items()
             if any(part.startswith(FLASH_PREFIX) for part in path)}
    if not flash and any(len(path) > 1 and path[-1] == "pallas_call" and path[-2] != UNNAMED_KERNEL
                         for path in parts.values()):
        return None  # the program names its kernels, and none of them is a flash kernel
    runs = trace.per_step(dev, lambda op: op[2] == xplane.MOSAIC_TARGET and (
        not flash or op[0] in flash))
    return median(runs) / 1e6 if runs else None


def exposed_collectives_ms_by_phase(trace: xplane.Trace,
                                    scopes: Dict[str, str]) -> Dict[str, float]:
    """`collectives.exposed_ms` by the phase each collective's scope puts it
    in: per step on the first device, the time a collective of that phase is
    in flight with no other operation running. Phases with none are left out."""
    if not trace.devices:
        return {}
    dev = trace.devices[0]
    others = xplane.union((op[4], op[4] + op[5]) for op in trace._leaf_ops(dev)
                          if not xplane.is_collective(op[1]))
    flying: Dict[str, List[xplane.Interval]] = {}
    for op in dev["ops"]:
        if xplane.is_collective(op[1]) and not op[1].endswith(("-start", "-done")):
            flying.setdefault(phase(scopes.get(op[0], "")), []).append((op[4], op[4] + op[5]))
    for name, opcode, start, dur in dev["async"]:
        if xplane.is_collective(opcode):
            flying.setdefault(phase(scopes.get(name, "")), []).append((start, start + dur))
    runs = trace.step_runs(dev)
    out = {}
    for p, intervals in flying.items() if runs else ():
        out[p] = median(xplane.measure(xplane.subtract(xplane.clip(intervals, s, s + d), others))
                        for _, _, s, d in runs) / 1e6
    return out


def idle_by_program_span(trace: xplane.Trace, spans: List[List]) -> List[Tuple[str, float]]:
    """Idle seconds of the first device by `<bench span>/<ray_tpu span>`: each
    gap goes to the benchmark's span `Trace.attribute` gives it, and inside
    the gap every nanosecond to the innermost (shortest) `ray_tpu.*` span that
    covers it, or to the benchmark's span alone. Most first."""
    if not trace.devices:
        return []
    inner_first = sorted(spans, key=lambda s: s[2])
    total: Dict[str, float] = {}
    for gap in trace.idle_gaps(trace.devices[0]):
        outer = trace.attribute(gap)
        left = [gap]
        for name, start, dur, _ in inner_first:
            if not left or start >= gap[1] or start + dur <= gap[0]:
                continue
            mine = xplane.measure(xplane.clip(left, start, start + dur))
            if mine:
                key = f"{outer}/{name}"
                total[key] = total.get(key, 0.0) + mine / 1e9
                left = xplane.subtract(left, [(start, start + dur)])
        rest = xplane.measure(left)
        if rest:
            total[outer] = total.get(outer, 0.0) + rest / 1e9
    return sorted(total.items(), key=lambda kv: -kv[1])


# ------------------------------------------------------- what readers call
class ProgramTrace:
    """One run's device trace beside the program's own names for it."""

    def __init__(self, trace: xplane.Trace, raw: Dict[str, Any]):
        self.trace = trace
        self.scopes: Dict[str, str] = raw["scopes"]
        self.named = names_the_step(self.scopes)
        lo, hi = trace.window
        self.spans = [s for s in raw["program_spans"] if lo <= s[1] and s[1] + s[2] <= hi]
        self._phases: Optional[Dict[str, float]] = None

    def phase(self, name: str) -> Optional[float]:
        """Milliseconds per step of one phase, or nothing where the program
        carries no names."""
        if not self.named:
            return None
        if self._phases is None:
            self._phases = phase_ms(self.trace, self.scopes) or {}
        return self._phases.get(name)

    def kernel(self, name: str) -> Optional[float]:
        return kernel_ms(self.trace, self.scopes, name)

    def span_ms(self, name: str) -> List[float]:
        """Milliseconds of each `name` span inside the traced steps."""
        return [dur / 1e6 for label, _, dur, _ in self.spans if label == name]


def raw_trace_path(run: Dict[str, Any]) -> Optional[str]:
    """Rank 0's `.xplane.pb`, beside the table `WorkerRun.finish` wrote from it."""
    table = run["summary"].get("trace_table")
    if not table or not table.endswith(".trace.json"):
        return None
    out_dir, stem = os.path.split(table[:-len(".trace.json")])
    paths = glob.glob(os.path.join(out_dir, "trace", stem + ".rank0", "**", "*.xplane.pb*"),
                      recursive=True)
    return paths[0] if paths else None


def flash_ms_of(run: Dict[str, Any]) -> Optional[float]:
    """What `kernels.flash_ms` and `kernels.flash_roofline` divide by: the
    run's `flash_ms` by the names in its raw trace. Without the raw trace the
    Mosaic calls cannot be told apart, and nothing is read (as for
    `kernels.flash_fwd_ms` and `kernels.flash_bwd_ms`)."""
    program = of(run)
    return flash_ms(program.trace, program.scopes) if program else None


def of(run: Dict[str, Any]) -> Optional[ProgramTrace]:
    """The run's `ProgramTrace`, or nothing where it was not traced; kept on
    `run`, which `driver.result_line` hands to every reader in turn. The first
    call prints the `[run]` lines only this file can write and leaves them in
    the run's summary (and so in `out/<cell>.<seed>.json`): idle seconds by
    program span, the step's phases, and, where the step has collectives,
    their exposed time by phase."""
    if "program_trace" in run:
        return run["program_trace"]
    trace = run.get("device_trace")
    path = raw_trace_path(run) if trace is not None else None
    program = run["program_trace"] = (
        ProgramTrace(trace, read_xplane(path)) if path is not None else None)
    if program is None:
        return None
    idle = idle_by_program_span(trace, program.spans)[:10]
    run["summary"]["idle_by_program_span"] = idle
    print(f"[run] idle seconds by program span {json.dumps(idle)}")
    if program.named:
        phases = {p: program.phase(p) for p in PHASES}
        exposed = exposed_collectives_ms_by_phase(trace, program.scopes)
        run["summary"]["phase_ms"] = phases
        print(f"[run] step phases ms/step {json.dumps(phases)} of step.device_ms "
              f"{trace.step_device_ms()}")
        if exposed:
            run["summary"]["collectives_exposed_ms_by_phase"] = exposed
            print(f"[run] collectives.exposed_ms by phase {json.dumps(exposed)}")
    return program
