"""One account of the traced step's device time, operation by operation, from
the raw trace alone: who owns each operation, what class of work it is, and
what XLA counted for it.

`program_trace.py` keeps two stats of a device plane's event metadata (`tf_op`,
`program_id`). The same entries carry `hlo_category`, `flops`, `bytes_accessed`
and `source` (a Python `file:line`) for every instruction, and the plane
`/host:metadata` holds the step's whole `Hlo Proto` (stat bytes of the entry
whose id is the program's), so every fused instruction's own `op_name`, its
operands and its result shape are there to read (looked at by hand, PR 53, on
`testdata/tiny_gpt_named_v5e`: 401 entries with the stats, 216 computations,
156 fusions). This file reads them with `program_trace`'s wire reader and says:

owner, by the first rule that gives one (`RULES`):
  scan   what jax's own scan emits directly under `.../while/body/` with no
         scope of the program behind it: the stacking and unstacking of
         residuals (`dynamic_update_slice`, `dynamic_slice`, ...) is
         `scan_carry`, the transposed pass's `add_any` `grad_accumulate`; what
         the compiler made of the loop's carries (asynchronous slices, casts
         hoisted out of the body) carries the `while`'s own name: `scan_carry`;
  name   the innermost component of its own `op_name` that is not jax's
         (`JAX_WORDS`, `jit(...)`, an einsum's spec, the trailing primitive;
         `jvp(x)` and `transpose(jvp(x))` are `x`). No list of the models'
         scopes: a new model's scope shows up without an edit;
  work   for a fusion, the scopes that hold most of its fused products' flops,
         else most of its fused instructions' result bytes. The fusion is
         misfiled where its root's owner and its work's owner are not one
         scope or a scope and one around it: today's `scope_ms` readings
         charge it to the root's;
  operand / user   for an operation with no name (`copy`, `copy-start`,
         `copy-done`, `bitcast`, what the compiler made), the owner of the
         producer of its largest operand, else of its user (of several, the
         one with the largest result);
  else `unowned`.

class, from libtpu's `hlo_category` (`CLASS_OF_CATEGORY`; one it does not list
is `unclassed`) and, for a fusion, from what it holds: `product` with a
`convolution` or `dot` inside, `movement` with no arithmetic inside.

XLA's `bytes_accessed` is a count and no floor (operands in VMEM count as HBM
traffic: over the named fixture the sum of max(flops / peak, bytes / rate) is
180 us for the 108 us the operations run), so the table labels it so, nothing
divides by it, and the one floor here is `flops` over the MXU's peak.

Arithmetic on the table `xplane.extract` made and the raw trace beside it; the
parent runs it after the window, under `--trace 1` alone.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import time
from typing import Any, Dict, Iterable, List, Optional, Tuple

from benchmark.harness import xplane
from benchmark.harness.program_trace import _fields, _plane, _stat, _text, phase, raw_trace_path

# The order decides who gets a nanosecond that two classes cover at once.
CLASSES = ("product", "kernel", "elementwise", "collective", "movement", "unclassed")
# libtpu's categories as the nine cells' traces and the two recorded ones carry them (PR 53). A collective is
# known by its opcode, a custom call by its target; a category that is not here is `unclassed` and printed.
CLASS_OF_CATEGORY = {
    "convolution fusion": "product",
    "loop fusion": "elementwise", "custom fusion": "elementwise", "non-fusion elementwise": "elementwise",
    "reduce": "elementwise", "reduce-window": "elementwise", "sort": "elementwise",
    "all-reduce-scatter fusion": "collective",  # an all-reduce and each chip's slice of it: FSDP's gradients
    "async-start": "movement", "async-done": "movement",  # a collective where what they wrap is one, else a slice
    "data formatting": "movement", "copy-start": "movement", "copy-done": "movement",
    "dynamic-update-slice": "movement", "slice": "movement", "broadcast": "movement", "iota": "movement",
    "pad": "movement", "concatenate": "movement",
}
CLASS_OF_TARGET = {xplane.MOSAIC_TARGET: "kernel", "AllocateBuffer": "movement", "ConcatBitcast": "movement"}
PRODUCTS = ("convolution", "dot")
# Opcodes that compute nothing: a fusion of these alone moves data.
NO_ARITHMETIC = frozenset((
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast", "copy", "slice", "dynamic-slice",
    "dynamic-update-slice", "pad", "concatenate", "transpose", "reshape", "broadcast", "iota", "gather",
    "convert", "bitcast-convert"))
NO_WORK = frozenset(("parameter", "constant", "tuple", "get-tuple-element", "bitcast"))
ADDRESSING = ("dynamic-slice", "slice")  # inside a fusion a slice is where its user reads, not a pass of its own
JAX_WORDS = frozenset((
    "while", "body", "cond", "closed_call", "core_call", "checkpoint", "rematted_computation", "remat",
    "shard_map", "pallas_call", "pjit", "custom_jvp_call", "custom_lin"))
JAX_PREFIXES = ("branch_", "custom_vjp_call")
# What a scan's own body does to its carry and its stacked inputs and outputs.
SCAN_MOVES = frozenset((
    "dynamic_update_slice", "dynamic_slice", "copy", "squeeze", "reshape", "broadcast_in_dim",
    "convert_element_type", "select_n", "concatenate", "slice"))
RULES = ("scan", "name", "work", "operand", "user", "none")
UNOWNED, SCAN_CARRY, GRAD_ACCUMULATE = "unowned", "scan_carry", "grad_accumulate"
ROWS = 25
ELEMENT_BYTES = {1: 1, 2: 1, 3: 2, 4: 4, 5: 8, 6: 1, 7: 2, 8: 4, 9: 8, 10: 2, 11: 4, 12: 8, 15: 8, 16: 2,
                 18: 16, 19: 1, 20: 1, 21: 1, 22: 1, 23: 1, 24: 1, 25: 1}  # xla_data.proto PrimitiveType
_WRAPPED = re.compile(r"(\w+)\((.*)\)")


# ------------------------------------------------------------------- owners
def program_scope(component: str) -> Optional[str]:
    """The program's own name in one component of an `op_name`, or nothing
    where the component is jax's: a transformation wraps the scope entered
    under it (`transpose(jvp(blocks))` is `blocks`), `jit(...)` names a
    function jax traced, `bsd,vd->bsv` is an einsum."""
    while True:
        m = _WRAPPED.fullmatch(component)
        if m is None:
            break
        if m.group(1) == "jit":
            return None
        component = m.group(2)
    if (not component or component in JAX_WORDS or component.startswith(JAX_PREFIXES)
            or "->" in component or "<locals>" in component):  # an einsum's spec; a function's qualified name
        return None
    return component


def scope_path(op_name: str) -> Tuple[str, ...]:
    """The program's scopes of an `op_name`, outermost first, without its
    trailing primitive; what the scan's own machinery emits ends in
    `scan_carry` or `grad_accumulate`."""
    parts = op_name.split("/")
    path = tuple(s for s in map(program_scope, parts[:-1]) if s)
    if parts[-1] == "while":  # the loop's own name on another instruction: what the compiler made of its carries
        return path + (SCAN_CARRY,)
    if "body" in parts[:-1]:
        at = len(parts) - 2 - parts[-2::-1].index("body")
        if at and parts[at - 1] == "while" and not any(map(program_scope, parts[at + 1:-1])):
            if parts[-1] == "add_any":
                return path + (GRAD_ACCUMULATE,)
            if parts[-1] in SCAN_MOVES:
                return path + (SCAN_CARRY,)
    return path


def _related(a: Tuple[str, ...], b: Tuple[str, ...]) -> bool:
    """Whether the innermost scope of one path is on the other: one scope, or
    a scope and one around it."""
    return not a or not b or a[-1] in b or b[-1] in a


# ------------------------------------------------------------ the raw trace
def _ints(wire: int, value) -> List[int]:
    """A repeated integer field's values: packed (one length-delimited run of
    varints) or one varint a field."""
    if wire != 2:
        return [value]
    out, i, n = [], 0, len(value)
    while i < n:
        x = shift = 0
        while True:
            b = value[i]
            i += 1
            x |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                break
        out.append(x)
    return out


def _shape(buf) -> Tuple[int, List[int]]:
    """(bytes, dimensions of the first array) of a ShapeProto (element_type=2,
    dimensions=3, tuple_shapes=4): a tuple's bytes are its leaves'."""
    kind, dims, leaves = 0, [], []
    for f, wire, v in _fields(buf):
        if f == 2:
            kind = v
        elif f == 3:
            dims += _ints(wire, v)
        elif f == 4:
            leaves.append(_shape(v))
    if leaves:
        return sum(b for b, _ in leaves), leaves[0][1]
    size = ELEMENT_BYTES.get(kind, 0)
    for d in dims:
        size *= d
    return size, dims


def _instruction(buf) -> Dict[str, Any]:
    """One HloInstructionProto: name=1, opcode=2, shape=3, metadata=7 (OpMetadata.op_name=2),
    convolution_dimension_numbers=16 (kernel_output_feature_dimension=4), custom_call_target=28,
    dot_dimension_numbers=30 (lhs_contracting_dimensions=1), id=35, operand_ids=36,
    called_computation_ids=38."""
    inst: Dict[str, Any] = {"name": "", "opcode": "", "op_name": "", "bytes": 0, "dims": [], "operands": [],
                            "called": [], "target": "", "id": 0}  # a field at its default (id 0) is not on the wire
    for f, wire, v in _fields(buf):
        if f == 1:
            inst["name"] = _text(v)
        elif f == 2:
            inst["opcode"] = _text(v)
        elif f == 3:
            inst["bytes"], inst["dims"] = _shape(v)
        elif f == 7:
            inst["op_name"] = next((_text(x) for k, _, x in _fields(v) if k == 2), "")
        elif f == 16:
            inst["kernel_out"] = next((x for k, _, x in _fields(v) if k == 4), 0)
        elif f == 28:
            inst["target"] = _text(v)
        elif f == 30:
            inst["contracting"] = [x for k, w, x0 in _fields(v) if k == 1 for x in _ints(w, x0)]
        elif f == 35:
            inst["id"] = v
        elif f == 36:
            inst["operands"] += _ints(wire, v)
        elif f == 38:
            inst["called"] += _ints(wire, v)
    return inst


def link(computations: Dict[int, List[Dict[str, Any]]]) -> Dict[str, Any]:
    """{"computations": {id: [instruction, ...]}, "by_id", "by_name"}: a module's
    instructions by their ids and names (unique in a module), each with its
    users."""
    by_id = {i["id"]: i for insts in computations.values() for i in insts}
    for instructions in computations.values():
        for inst in instructions:
            inst.setdefault("users", [])
            for operand in inst["operands"]:
                by_id[operand].setdefault("users", []).append(inst["id"])
    return {"computations": computations, "by_id": by_id, "by_name": {i["name"]: i for i in by_id.values()}}


def read_module(proto) -> Dict[str, Any]:
    """`link` of an `Hlo Proto`'s computations (HloProto.hlo_module=1 >
    HloModuleProto.computations=3 > HloComputationProto instructions=2, id=5)."""
    computations: Dict[int, List[Dict[str, Any]]] = {}
    for f, _, module in _fields(proto):
        if f != 1:
            continue
        for g, _, computation in _fields(module):
            if g != 3:
                continue
            key, instructions = 0, []
            for h, _, v in _fields(computation):
                if h == 2:
                    instructions.append(_instruction(v))
                elif h == 5:
                    key = v
            computations[key] = instructions
    return link(computations)


def _metadata(events, stat_names) -> Dict[Any, Dict[str, Dict[str, Any]]]:
    """{program id: {instruction: {op_name, category, flops, bytes, source}}}
    of a device plane's event metadata (display_name=4, stats=5)."""
    programs: Dict[Any, Dict[str, Dict[str, Any]]] = {}
    for meta in events.values():
        display, stats = "", {}
        for k, _, v in _fields(meta):
            if k == 4:
                display = _text(v)
            elif k == 5:
                key, value = _stat(v, stat_names)
                stats[key] = value
        if not display or "hlo_category" not in stats:
            continue
        tf_op = stats.get("tf_op") or ""
        programs.setdefault(stats.get("program_id"), {})[display] = {
            "op_name": tf_op.rpartition(":")[0] or tf_op, "category": stats["hlo_category"],
            "flops": stats.get("flops") or 0, "bytes": stats.get("bytes_accessed") or 0,
            "source": _strip(stats.get("source") or "")}
    return programs


def _strip(source: str) -> str:
    """A `source` from the repo's root on: the checkout's own path says nothing."""
    at = source.find("ray_tpu/")
    return source[at:] if at > 0 else source


def read(path: str) -> Optional[Dict[str, Any]]:
    """{"stats": {instruction: ...} of the step's program (the one with the
    most named instructions, as `program_trace.read_xplane` picks it),
    "module": `read_module` of that program's `Hlo Proto` or an empty one}, or
    nothing where the trace has no `/device:TPU:` plane (a CPU rehearsal's)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as fh:
        space = memoryview(fh.read())
    programs: Dict[Any, Dict[str, Dict[str, Any]]] = {}
    protos: Dict[Any, Any] = {}
    for f, _, plane in _fields(space):
        if f != 1:
            continue
        name, _, events, stat_names = _plane(plane)
        if name.startswith("/device:TPU:"):
            for program, stats in _metadata(events, stat_names).items():
                programs.setdefault(program, {}).update(stats)
        elif name == "/host:metadata":
            protos.update(events)
    if not programs:
        return None
    step = max(programs, key=lambda p: (sum(bool(s["op_name"]) for s in programs[p].values()), len(programs[p])))
    module = link({})
    if step in protos:
        for k, _, stat in _fields(protos[step]):  # XEventMetadata.stats=5 > XStat.bytes_value=6
            if k == 5:
                module = next((read_module(v) for g, _, v in _fields(stat) if g == 6), module)
    return {"stats": programs[step], "module": module}


# ------------------------------------------------------------- the account
def _product_flops(inst: Dict[str, Any], by_id) -> float:
    """2 x result elements x contracted size of a `convolution` (the kernel's
    elements over its output features) or a `dot`."""
    elements = 1
    for d in inst["dims"]:
        elements *= d
    contracted = 1
    if inst["opcode"] == "convolution" and len(inst["operands"]) > 1:
        kernel = by_id[inst["operands"][1]]["dims"]
        for axis, d in enumerate(kernel):
            if axis != inst.get("kernel_out", 0):
                contracted *= d
    elif inst["operands"]:
        lhs = by_id[inst["operands"][0]]["dims"]
        for axis in inst.get("contracting", ()):
            contracted *= lhs[axis]
    return 2.0 * elements * contracted


class Owners:
    """Owner, rule, class and misfiling of every instruction of the step that
    can run, by the rules at the top of this file."""

    def __init__(self, raw: Dict[str, Any]):
        self.stats, self.module = raw["stats"], raw["module"]
        self._paths: Dict[str, Tuple[Tuple[str, ...], str, str]] = {}
        self._classes: Dict[str, str] = {}

    def _fused(self, inst) -> List[Dict[str, Any]]:
        return [i for c in inst["called"] for i in self.module["computations"].get(c, ())]

    def _wraps_a_collective(self, inst) -> bool:
        """Whether an `async-start` (or the `async-done` / `async-update` behind it) runs a collective."""
        while inst["opcode"] in ("async-done", "async-update") and inst["operands"]:
            inst = self.module["by_id"][inst["operands"][0]]
        return inst["opcode"] == "async-start" and any(xplane.is_collective(i["opcode"]) for i in self._fused(inst))

    def _own_name(self, name: str) -> str:
        inst = self.module["by_name"].get(name)
        return (self.stats.get(name) or {}).get("op_name") or (inst["op_name"] if inst else "")

    def _work(self, inst, slices_too: bool) -> Tuple[str, ...]:
        """The scope path that holds most of a fusion's products' flops, else
        most of its fused instructions' result bytes (an update in place
        counts what it writes; a slice is where its user reads and counts
        only with `slices_too`, for a fusion nothing else speaks for)."""
        by_id = self.module["by_id"]
        flops: Dict[Tuple[str, ...], float] = {}
        moved: Dict[Tuple[str, ...], float] = {}
        sliced: Dict[Tuple[str, ...], float] = {}
        for fused in self._fused(inst):
            path = scope_path(fused["op_name"]) if fused["opcode"] not in NO_WORK else ()
            if not path:
                continue
            if fused["opcode"] in PRODUCTS:
                flops[path] = flops.get(path, 0.0) + _product_flops(fused, by_id)
            written = fused["bytes"]
            if fused["opcode"] == "dynamic-update-slice" and len(fused["operands"]) > 1:
                written = by_id[fused["operands"][1]]["bytes"]
            votes = sliced if fused["opcode"] in ADDRESSING else moved
            votes[path] = votes.get(path, 0.0) + written
        votes = flops or moved or (sliced if slices_too else {})
        return max(votes, key=votes.get) if votes else ()

    def _own(self, name: str) -> Tuple[Tuple[str, ...], str, str]:
        """(scope path, rule, own `op_name`) by what the instruction itself
        carries: the rules `scan`, `work` and `name`, or no path."""
        inst, own = self.module["by_name"].get(name), self._own_name(name)
        path = scope_path(own) if own else ()
        if path and path[-1] in (SCAN_CARRY, GRAD_ACCUMULATE):
            return path, "scan", own
        work = self._work(inst, slices_too=not path) if inst and inst["opcode"] == "fusion" else ()
        if work and not (path and work[-1] in path):
            return work, "work", own
        return path, "name" if path else "none", own

    def _through(self, inst, towards: str, seen: set) -> Optional[Tuple[Tuple[str, ...], str]]:
        """(path, op_name) of the nearest producer that has an owner of its
        own, the largest operand's first (`towards` "operands"), or user, the
        largest result's first."""
        by_id = self.module["by_id"]
        for other in sorted((by_id[i] for i in inst[towards]), key=lambda i: -i["bytes"]):
            if other["id"] in seen:
                continue
            seen.add(other["id"])
            path, _, name = self._own(other["name"])
            if path:
                return path, name
            found = self._through(other, towards, seen)
            if found:
                return found
        return None

    def path(self, name: str) -> Tuple[Tuple[str, ...], str, str]:
        """(scope path, rule, the `op_name` it came by) of one instruction."""
        if name not in self._paths:
            found = self._own(name)
            inst = self.module["by_name"].get(name)
            for towards, rule in (("operands", "operand"), ("users", "user")) if inst and not found[0] else ():
                through = self._through(inst, towards, {inst["id"]})
                if through:
                    found = (through[0], rule, through[1])
                    break
            self._paths[name] = found
        return self._paths[name]

    def owner(self, name: str) -> str:
        path = self.path(name)[0]
        return path[-1] if path else UNOWNED

    def misfiled(self, name: str) -> bool:
        """A fusion whose work's owner and whose root's are unrelated scopes."""
        path, rule, own = self.path(name)
        return rule == "work" and bool(own) and not _related(scope_path(own), path)

    def klass(self, name: str, opcode: str = "", target: str = "") -> str:
        """The class of one instruction; `opcode` and `target` as the trace's
        table has them, for a trace that carries no `Hlo Proto`."""
        if name not in self._classes:
            self._classes[name] = self._klass(name, opcode, target)
        return self._classes[name]

    def _klass(self, name: str, opcode: str, target: str) -> str:
        inst = self.module["by_name"].get(name)
        if opcode == "custom-call":
            return CLASS_OF_TARGET.get(target, "unclassed")
        if xplane.is_collective(opcode) or (inst and self._wraps_a_collective(inst)):
            return "collective"
        found = CLASS_OF_CATEGORY.get((self.stats.get(name) or {}).get("category"), "unclassed")
        if inst and inst["opcode"] == "fusion" and found != "unclassed":
            opcodes = {i["opcode"] for i in self._fused(inst)}
            if opcodes.intersection(PRODUCTS):
                return "product"
            if opcodes <= NO_ARITHMETIC:
                return "movement"
        return found


class Account:
    """The step's device time of one run by owner, class and phase."""

    def __init__(self, trace: xplane.Trace, raw: Dict[str, Any], peaks: Dict[str, float]):
        self.owners, self.stats = Owners(raw), raw["stats"]
        self.flops_per_s, self.bytes_per_s = peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]
        dev = trace.devices[0]
        self.ops = trace._leaf_ops(dev)
        runs = trace.step_runs(dev)
        busy = [xplane.measure(xplane.union(xplane.clip(
            ((op[4], op[4] + op[5]) for op in self.ops), s, s + d))) for _, _, s, d in runs]
        # The steps at the median of `step.device_ms` (of eight, the two in the middle): every
        # reading is their mean, so that the classes add up to `step.device_ms` itself.
        order = sorted(range(len(runs)), key=busy.__getitem__)
        middle = order[(len(order) - 1) // 2:len(order) // 2 + 1]
        self.steps = [(runs[i][2], runs[i][2] + runs[i][3]) for i in middle]
        self.n_runs = len(runs)
        self.runs = [(s, s + d) for _, _, s, d in runs]
        self.klass = {op[0]: self.owners.klass(op[0], op[1], op[2]) for op in self.ops}
        # What this reader's two dicts do not know: a category of libtpu's, a custom call's target.
        self.unknown = sorted({
            f"custom-call:{op[2]}" if op[1] == "custom-call" else (self.stats.get(op[0]) or {}).get("category", "no stats")
            for op in self.ops if self.klass[op[0]] == "unclassed"})
        self.classes = self._class_ms()

    def _median_ms(self, groups: Iterable[List[xplane.Interval]]) -> List[float]:
        """For each group in turn, the ms a step it covers that no earlier group covers."""
        groups = list(groups)
        out = [0.0] * len(groups)
        for lo, hi in self.steps:
            covered: List[xplane.Interval] = []
            for n, group in enumerate(groups):
                mine = xplane.union(xplane.clip(group, lo, hi))
                out[n] += xplane.measure(xplane.subtract(mine, covered)) / 1e6 / len(self.steps)
                covered = xplane.union(covered + mine)
        return out

    def _class_ms(self) -> Dict[str, float]:
        """{class: ms a step}, a nanosecond two classes cover to the first of `CLASSES`."""
        by_class: Dict[str, List[xplane.Interval]] = {c: [] for c in CLASSES}
        for op in self.ops:
            by_class[self.klass[op[0]]].append((op[4], op[4] + op[5]))
        return dict(zip(CLASSES, self._median_ms(by_class[c] for c in CLASSES)))

    def picked_ms(self, pick) -> float:
        """The busy union a step of the operations `pick(name)` keeps."""
        return self._median_ms([[(op[4], op[4] + op[5]) for op in self.ops if pick(op[0])]])[0]

    def unowned_ms(self) -> float:
        return self.picked_ms(lambda name: self.owners.owner(name) == UNOWNED)

    def misfiled_ms(self) -> float:
        return self.picked_ms(self.owners.misfiled)

    def product_floor_ms(self) -> float:
        """The flops XLA counts for the step's `product` operations over the MXU's peak."""
        flops = sum((self.stats.get(op[0]) or {}).get("flops", 0) for op in self.ops
                    if self.klass[op[0]] == "product"
                    and any(lo <= op[4] < hi for lo, hi in self.steps))
        return flops / len(self.steps) / self.flops_per_s * 1e3

    def rows(self, by: str = "owner") -> List[Dict[str, Any]]:
        """Every operation of the traced steps by (owner or `source`, class,
        phase): calls and ms a step (the calls' own time summed, over the
        traced steps), the two counts of XLA's, the misfiled part; most first."""
        rows: Dict[Tuple[str, str, str], Dict[str, Any]] = {}
        for op in self.ops:
            if not any(lo <= op[4] and op[4] + op[5] <= hi for lo, hi in self.runs):
                continue
            name = op[0]
            stat = self.stats.get(name) or {}
            _, rule, via = self.owners.path(name)
            key = (self.owners.owner(name) if by == "owner" else stat.get("source") or "no source",
                   self.klass[name], phase(via))
            row = rows.setdefault(key, {
                by: key[0], "class": key[1], "phase": key[2], "calls": 0.0, "ms": 0.0, "floor_ms": 0.0,
                "xla_bytes_ms": 0.0, "misfiled_ms": 0.0, "names": {}, "rules": set(), "sources": set()})
            ms = op[5] / 1e6 / self.n_runs
            row["calls"] += 1 / self.n_runs
            row["ms"] += ms
            row["floor_ms"] += stat.get("flops", 0) / self.flops_per_s * 1e3 / self.n_runs
            row["xla_bytes_ms"] += stat.get("bytes", 0) / self.bytes_per_s * 1e3 / self.n_runs
            row["misfiled_ms"] += ms if self.owners.misfiled(name) else 0.0
            row["names"][name] = row["names"].get(name, 0.0) + ms
            row["rules"].add(rule)
            if rule not in ("scan", "name", "work") and stat.get("source"):
                row["sources"].add(stat["source"])
        out = sorted(rows.values(), key=lambda r: -r["ms"])
        return [{**r, "names": sorted(r["names"], key=lambda n: -r["names"][n])[:3],
                 "rules": sorted(r["rules"], key=RULES.index), "sources": sorted(r["sources"])[:3]} for r in out]


def print_rows(rows: List[Dict[str, Any]], by: str = "owner") -> None:
    print(f"[run] step account: ms a step, calls, flops / peak (the product floor), XLA's bytes_accessed / HBM's "
          f"rate (XLA's count, not a floor), misfiled ms, by {by}, class and phase")
    for r in rows:
        print(f"[run] {r['ms']:9.3f} ms {r['calls']:7.1f} x  floor {r['floor_ms']:8.3f}  xla bytes "
              f"{r['xla_bytes_ms']:8.3f}  misfiled {r['misfiled_ms']:7.3f}  {r[by]:<24} {r['class']:<11} "
              f"{r['phase']:<9} {'+'.join(r['rules'])}  [{', '.join(r['names'])}]"
              + (f"  at {', '.join(r['sources'])}" if r["sources"] else ""))


def of(run: Dict[str, Any]) -> Optional[Account]:
    """The run's `Account`, or nothing where it was not traced or its trace
    has no `/device:TPU:` plane; kept on `run` like `program_trace.of`. The
    first call prints the table's `ROWS` longest rows and leaves them, the
    classes and the reader's own seconds in the run's summary (and so in
    `out/<cell>.<seed>.json`)."""
    if "step_account" in run:
        return run["step_account"]
    run["step_account"] = None
    trace = run.get("device_trace")
    path = raw_trace_path(run) if trace is not None and trace.devices else None
    if path is None or not run.get("peaks"):
        return None
    t = time.perf_counter()
    raw = read(path)
    if raw is None or not trace.step_runs(trace.devices[0]):
        return None
    account = run["step_account"] = Account(trace, raw, run["peaks"])
    classes, rows = account.classes, account.rows()
    seconds = time.perf_counter() - t
    run["summary"]["step_account"] = {"classes": classes, "rows": rows[:ROWS], "reader_s": seconds,
                                      "unclassed_categories": account.unknown}
    print_rows(rows[:ROWS])
    print(f"[run] step account classes ms/step {json.dumps(classes)} of step.device_ms "
          f"{trace.step_device_ms()}; categories this reader does not know {account.unknown}; "
          f"read in {seconds:.2f} s")
    return account


# ---------------------------------------------------------- the other ranks
def rank_paths(run: Dict[str, Any]) -> List[str]:
    """The raw traces of ranks 1, 2, ... beside rank 0's, in their order."""
    first = raw_trace_path(run)
    if first is None:
        return []
    stem = first[:first.index(".rank0" + os.sep)]
    out = []
    while True:
        paths = glob.glob(os.path.join(f"{stem}.rank{len(out) + 1}", "**", "*.xplane.pb*"), recursive=True)
        if not paths:
            return out
        out.append(paths[0])


def ranks(run: Dict[str, Any]) -> Optional[List[Dict[str, Optional[float]]]]:
    """`step.device_ms`, `collectives.total_ms` and `collectives.exposed_ms`
    of every rank, each on its own rank's clock (a quantity of one rank needs
    no clock across processes, and none is invented): rank 0's from the table
    the run already holds, the others' from their raw traces, read as rank 0's
    was (`xplane.extract`: jax's `ProfileData`, imported here after the gang
    has let go of its chips). Kept on `run`; printed on the first call."""
    if "rank_account" in run:
        return run["rank_account"]
    run["rank_account"] = None
    trace = run.get("device_trace")
    if trace is None or not trace.devices:
        return None
    out = []
    for one in [trace] + [xplane.Trace(xplane.extract(p)) for p in rank_paths(run)]:
        both = one.collectives_ms() or (None, None)
        out.append({"step.device_ms": one.step_device_ms(), "collectives.total_ms": both[0],
                    "collectives.exposed_ms": both[1]})
    run["rank_account"] = run["summary"]["rank_account"] = out
    print(f"[run] ranks 0..{len(out) - 1}, each on its own clock: " + "; ".join(
        f"{key} {json.dumps([r[key] for r in out])}" for key in out[0]))
    return out
