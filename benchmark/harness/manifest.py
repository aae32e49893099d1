"""`BENCHMARK.json` and the files it names. Everything that belongs to one
configuration, one mix or one per-layer metric is a file of its own, found
here by name, so that a later PR adds files and appends entries and edits
nothing that is there.

A per-layer entry with a `workloads` list takes no later cell, and only a
`benchmark` PR may edit it. So a later cell brings such a reading as a copy,
an appended entry `<metric>.<configuration>` and a three-line reader file
(`tests/benchmark/widened_manifest.py` rehearses it), and the copy lives
until the next `benchmark` PR puts the cell on the listed entry's own list and
deletes the file: one entry and one reader file a reading (PR 50 folded 53
copies of 16 readings; `tests/benchmark/listed_readings.py` holds the lists)."""

from __future__ import annotations

import glob
import importlib.util
import json
import os
import re
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
MAX_CELLS = 24


class Manifest:
    def __init__(self, root: str = ROOT):
        self.root = root
        with open(os.path.join(root, "BENCHMARK.json")) as fh:
            self.data: Dict[str, Any] = json.load(fh)
        self.dir = os.path.join(root, self.data["paths"][0])

    # ---- cells
    def cell(self, name: str) -> Dict[str, Any]:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; there are "
                       f"{[w['name'] for w in self.data['workloads']]}")

    def config(self, name: str) -> Dict[str, Any]:
        """The configuration's file: the entry in `configs`, or, for one that no
        cell uses (the rehearsal's), `<dir>/configs/<name>.json`."""
        path = next((c["file"] for c in self.data["configs"] if c["name"] == name),
                    os.path.join(os.path.basename(self.dir), "configs", name + ".json"))
        with open(os.path.join(self.root, path)) as fh:
            return json.load(fh)

    def traffic(self, name: str) -> Dict[str, Any]:
        with open(os.path.join(self.dir, "traffic", name + ".json")) as fh:
            return json.load(fh)

    # ---- metrics
    def metrics_for(self, cell: str, level: str) -> List[Dict[str, Any]]:
        """Entries of `end_to_end` or `per_layer` that this cell reports."""
        return [m for m in self.data[level] if cell in m.get("workloads", [cell])]

    def layer_readers(self) -> Dict[str, Any]:
        """name -> module, one per file in `<dir>/layer_metrics/`."""
        readers = {}
        for path in sorted(glob.glob(os.path.join(self.dir, "layer_metrics", "*.py"))):
            spec = importlib.util.spec_from_file_location(
                "benchmark_layer_metric_" + os.path.basename(path)[:-3], path)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            readers[module.META["name"]] = module
        return readers


def reduced_problems(entry: Dict[str, Any], config: Dict[str, Any]) -> List[str]:
    """How a configuration cut to the chip writes the cut down, held to by the
    contract check and the tests alike. `reduced`, in the entry of
    `BENCHMARK.json` and in the configuration's file, is the same list of the
    keys changed from the source, by name. For each, the file holds the value
    as it is run under the key and the source's under `published`, so that
    `key: published -> here` is there to read. A cut configuration names its
    public source, the same in both places, and says in `layout.deployment`
    what deployment it stands for. Which keys may be cut at all (depth, never
    a width) is the `model-configs` guide's and the driver's to hold, not
    guessed here from the key's spelling."""
    out = []
    reduced = entry.get("reduced")
    if not isinstance(reduced, list) or reduced != config.get("reduced"):
        return [f"`reduced` is {reduced!r} in BENCHMARK.json and "
                f"{config.get('reduced')!r} in its file"]
    published = config.get("published", {})
    for key in reduced:
        if not isinstance(key, str) or not NAME.match(key):
            out.append(f"`reduced` names {key!r}: not a key's name")
        elif key not in config or key not in published or published[key] == config[key]:
            out.append(f"reduced key {key!r}: the file holds no `{key}` beside a different "
                       f"`published.{key}`")
    if reduced:
        source = entry.get("source", "")
        if not source.startswith(("http://", "https://")) or config.get("source") != source:
            out.append("cut, and its `source` is no URL or differs between BENCHMARK.json and its file")
        if not str(config.get("layout", {}).get("deployment", "")).strip():
            out.append("cut, and its file has no `layout.deployment` sentence")
    return out


def problems(m: Manifest) -> List[str]:
    """What the contract's checks that need no chip would refuse."""
    d, out = m.data, []
    want = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    if set(d) != want:
        out.append(f"keys {sorted(set(d) ^ want)} missing or unknown")
    for level in ("end_to_end", "per_layer"):
        for e in d[level]:
            if not NAME.match(e["name"]) or not UNIT.match(e["unit"]):
                out.append(f"{level} {e['name']!r}: bad name or unit {e['unit']!r}")
            if e["better"] not in ("lower", "higher") or e["source"] not in SOURCES:
                out.append(f"{level} {e['name']!r}: bad better/source")
            for w in e.get("workloads", []):
                if w not in [c["name"] for c in d["workloads"]]:
                    out.append(f"{level} {e['name']!r}: unknown workload {w!r}")
    e2e = {e["name"] for e in d["end_to_end"]}
    for e in d["end_to_end"]:
        if not 0 < e["bound"] <= 0.1 or e["source"] not in ("host_clock", "device_trace"):
            out.append(f"end_to_end {e['name']!r}: bound or source outside the contract")
        if set(e) - {"name", "unit", "better", "bound", "source", "workloads"}:
            out.append(f"end_to_end {e['name']!r}: unknown keys")
    if "setup_s" not in e2e:
        out.append("no setup_s")
    readers = m.layer_readers()
    for e in d["per_layer"]:
        if set(e) - {"name", "unit", "better", "source", "layer", "moves", "workloads"}:
            out.append(f"per_layer {e['name']!r}: unknown keys")
        if e["moves"] not in e2e:
            out.append(f"per_layer {e['name']!r} moves unknown {e['moves']!r}")
        meta = getattr(readers.get(e["name"]), "META", None)
        if meta is None:
            out.append(f"per_layer {e['name']!r}: no reader in layer_metrics/")
        elif any(meta[k] != e[k] for k in ("name", "unit", "better", "source", "layer", "moves")):
            out.append(f"per_layer {e['name']!r}: its reader's META disagrees with BENCHMARK.json")
    names = [e["name"] for e in d["end_to_end"] + d["per_layer"]]
    if len(names) != len(set(names)):
        out.append("two metrics share a name")
    configs = {c["name"]: c for c in d["configs"]}
    for c in d["configs"]:
        if not NAME.match(c["name"]) or not os.path.isfile(os.path.join(m.root, c["file"])):
            out.append(f"config {c['name']!r}: bad name or missing file {c['file']!r}")
            continue
        if not any(c["file"].startswith(p + "/") for p in d["paths"]):
            out.append(f"config {c['name']!r}: file outside paths")
        out += [f"config {c['name']!r}: {what}" for what in reduced_problems(c, m.config(c["name"]))]
    pairs = set()
    for w in d["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            out.append(f"workload {w.get('name')!r}: keys other than the contract's")
        if not NAME.match(w["name"]) or not NAME.match(w["traffic"]) or w["chips"] not in (1, 4):
            out.append(f"workload {w['name']!r}: bad name, traffic or chips")
        if w["config"] not in configs:
            out.append(f"workload {w['name']!r}: unknown config {w['config']!r}")
        if not os.path.isfile(os.path.join(m.dir, "traffic", w["traffic"] + ".json")):
            out.append(f"workload {w['name']!r}: no traffic file")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"] or "\t" in w["why"]:
            out.append(f"workload {w['name']!r}: why is not one line of at most 200 characters")
        if (w["config"], w["traffic"]) in pairs:
            out.append(f"workload {w['name']!r}: repeats a configuration and traffic pair")
        pairs.add((w["config"], w["traffic"]))
        for level in ("end_to_end", "per_layer"):
            if not [x for x in m.metrics_for(w["name"], level) if x["name"] != "setup_s"]:
                out.append(f"workload {w['name']!r}: reports no {level} metric besides setup_s")
    if set(configs) - {w["config"] for w in d["workloads"]}:
        out.append("a configuration that no cell uses")
    four = sum(w["chips"] == 4 for w in d["workloads"])
    if four > max(1, len(d["workloads"]) // 4):
        out.append(f"{four} four-chip cells")
    if not 1 <= len(d["workloads"]) <= MAX_CELLS:
        out.append(f"{len(d['workloads'])} cells: 1 to {MAX_CELLS}")
    if not 1 <= d["run_seconds"] <= 51 or len(json.dumps(d)) > 64 * 1024:
        out.append("run_seconds or size outside the contract")
    return out
