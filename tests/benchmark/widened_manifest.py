"""The two manifests the contract tests hold: the live one at the root of the
repo, and a copy widened the way a later `model_config` PR widens it, by new
files and appended entries alone (`test_benchmark_widening.py` is its dress
rehearsal; the contract tests of `test_benchmark_manifest.py` and
`test_benchmark_program_trace.py` take either through `manifest_root`). And
the two recorded traces as a reader's `run`. Fixtures, imported by name into
the test files that use them: a `conftest.py` here would shadow `tests/conftest.py`,
from which three test files import."""

import gzip
import json
import os
import shutil
import sys
from types import SimpleNamespace

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

TESTDATA = os.path.join(REPO, "benchmark", "testdata")
NAMED = os.path.join(TESTDATA, "tiny_gpt_named_v5e.xplane.pb.gz")    # PR 24's: the kernels carry names
UNNAMED = os.path.join(TESTDATA, "tiny_gpt_3_steps_v5e.xplane.pb.gz")  # PR 22's: none does
OTHER_KERNEL = "grouped_matmul"  # a Mosaic kernel's `pl.pallas_call(name=...)` that is no flash kernel
FED_CELL = "gpt2-medium.fed"  # the one-chip fed cell whose listed readings the rehearsal's cell copies

MODEL_MODULE = '''"""A model module of another name: what `worker.build_system` and the readers
ask of one. This one trains GPT-2's block; a real one brings its own `System`,
plain reference and `check`, and the FLOPs and bytes of each kernel it names."""

from benchmark.models.gpt2 import (  # noqa: F401
    System, build, check, flash_bytes_per_step, flash_flops_per_step, train_flops_per_token)


def grouped_matmul_flops_per_step(c, rows, seq):
    return 6.0 * 2 * rows * seq * c["n_embd"] * 4 * c["n_embd"] * c["n_layer"]
'''

# A reader file is its META, which its entry in BENCHMARK.json repeats, and a `read(run)`.
STEPS_READER = '''META = {meta}


def read(run):
    return run["summary"]["completed"]
'''
KERNEL_READER = '''"""Device time per step of the Mosaic calls named `{kernel}`."""

from benchmark.harness import program_trace

META = {meta}


def read(run):
    program = program_trace.of(run)
    return program.kernel("{kernel}") if program else None
'''
# An entry that lists its cells takes no later cell, and no PR but a `benchmark` one may
# edit it: a later fed cell brings the same reading under a name of its own, and the next
# `benchmark` PR puts the cell on the listed entry's list and deletes the copy (PR 50 folded 53).
SAME_READING = '''"""`{listed}` in `{cell}`: that entry lists its cells and a later cell cannot
append itself, so the cell brings the same reading under a name of its own, until a
`benchmark` PR puts the cell on that entry's list and deletes this file."""

from benchmark.layer_metrics import {stem} as listed

META = {{**listed.META, "name": "{name}"}}
read = listed.read
'''


def _metric(name, unit, better, source, layer):
    return {"name": name, "unit": unit, "better": better, "source": source, "layer": layer,
            "moves": "tokens_per_s_per_chip"}


PER_LAYER_CAP = 128  # the contract's, on the `per_layer` of the live `BENCHMARK.json`
REHEARSALS_TAG = "throwaway"  # what the name of everything `widen` appends starts with, its cell's too


def rehearsals_own(per_layer):
    """The entries a rehearsal appended, nobody's readings: those that list a `throwaway*` cell."""
    return [e for e in per_layer if any(cell.startswith(REHEARSALS_TAG) for cell in e.get("workloads", ()))]


def room(per_layer):
    """How many entries a `model_config` PR may still append; under 0, how many are over. The one count of it: the
    contract's cap, which is on the live `BENCHMARK.json`, less the entries that are no rehearsal's own. Nothing is set
    aside for the rehearsals' copies: `manifest.problems()` has no count of `per_layer`, no test holds a widened copy to
    the cap, and no driver reads a copy that `widen()` made under `tmp_path`. So it is one number on the live manifest
    and on a copy widened before, where `test_benchmark_widening.py` runs this directory's tests again: 128 may be held."""
    return PER_LAYER_CAP - (len(per_layer) - len(rehearsals_own(per_layer)))


def hold_the_room(per_layer):
    """The one bound on `per_layer`, held by `test_benchmark_manifest.py` alone."""
    left = room(per_layer)
    assert left >= 0, (
        f"`per_layer` holds {PER_LAYER_CAP - left} entries (a rehearsal's own `{REHEARSALS_TAG}*` entries not counted) "
        f"and may hold {PER_LAYER_CAP}, the contract's cap on the live `BENCHMARK.json`: {-left} over, so a "
        f"`model_config` PR may append none. No `model_config` PR can make room: a `benchmark` PR does, by folding the "
        f"`<metric>.<configuration>` copies accepted since the last one into the listed entries' `workloads` and "
        f"deleting their reader files (PERF.md section 4, `tests/benchmark/listed_readings.py`)")


def files_under(root):
    return sorted(os.path.join(d, f) for d, _, fs in os.walk(root) for f in fs)


def widen(root, base=REPO):
    """What the next `model_config` PR does, done to a copy: `benchmark/` and
    `BENCHMARK.json` of `base` copied under `root` (with a link to the program,
    which a checkout holds beside them), then a model module, a configuration
    cut in depth that uses it, a mix, a fed cell, two per-layer metrics of its
    own and, for each entry that lists the one-chip fed cell, the same reading
    under the new cell's name. Nothing here is read from the repo's manifest
    but what a PR would read there, and `base` may be a copy widened before.
    Returns the roots, the copied files as they were before anything was
    added, and the names of what was added."""
    from benchmark.harness.manifest import Manifest

    shutil.copytree(os.path.join(base, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(base, "BENCHMARK.json"), root)
    os.symlink(os.path.join(REPO, "ray_tpu"), os.path.join(root, "ray_tpu"))
    bench = os.path.join(root, "benchmark")
    before = {p: open(p, "rb").read() for p in files_under(bench)}
    tag = next(t for t in (REHEARSALS_TAG, REHEARSALS_TAG + "2", REHEARSALS_TAG + "3")
               if not os.path.exists(os.path.join(bench, "configs", t + "-cut.json")))
    name, mix_name, model, cell = f"{tag}-cut", f"{tag}-short-docs", f"{tag}_moe", f"{tag}-cut.short"
    added = set()

    def write(relative, text):
        with open(os.path.join(bench, relative), "x") as fh:  # "x": never over a file that is there
            fh.write(text)
        added.add("benchmark/" + relative)

    source = f"https://example.org/{tag}/blob/main/config.json"
    config = json.load(open(os.path.join(bench, "configs", "gpt2-nano.json")))
    config.update(
        name=name, source=source, model=model, n_head=4, n_layer=1,
        reduced=["n_layer"], published={"n_layer": 2}, batch={"global_rows": 4, "seq": 32},
        layout={**config["layout"], "deployment": "one worker on one chip holding one layer of the "
                "source's two with everything else whole: the depth is what the chip forces"})
    write(f"configs/{name}.json", json.dumps(config, indent=1))
    mix = json.load(open(os.path.join(bench, "traffic", "fed.json")))
    mix.update(name=mix_name, block_rows=16)
    mix["documents"].update(median_tokens=20, max_tokens=200)
    write(f"traffic/{mix_name}.json", json.dumps(mix, indent=1))
    write(f"models/{model}.py", MODEL_MODULE)

    manifest = json.load(open(os.path.join(root, "BENCHMARK.json")))
    manifest["configs"].append({
        "name": name, "source": source, "file": f"benchmark/configs/{name}.json",
        "reduced": ["n_layer"], "why": "another model module, one layer of two"})
    manifest["workloads"].append({
        "name": cell, "config": name, "traffic": mix_name, "chips": 1,
        "why": "4 x 32 tokens of short documents from 16-row blocks: the path a new configuration takes"})
    metrics = [_metric(f"{tag}.steps", "steps", "higher", "program_counter", "step"),
               _metric(f"kernels.{tag}_gmm_ms", "ms/step", "lower", "device_trace", "kernels")]
    write(f"layer_metrics/{tag}_steps.py", STEPS_READER.format(meta=json.dumps(metrics[0], indent=1)))
    write(f"layer_metrics/kernels_{tag}_gmm_ms.py",
          KERNEL_READER.format(meta=json.dumps(metrics[1], indent=1), kernel=OTHER_KERNEL))
    readers = Manifest(base).layer_readers()
    for listed in [e for e in manifest["per_layer"] if FED_CELL in e.get("workloads", ())]:
        stem = os.path.basename(readers[listed["name"]].__file__)[:-len(".py")]
        metrics.append({**listed, "name": f"{listed['name']}.{name}"})
        write(f"layer_metrics/{stem}_{name}.py", SAME_READING.format(
            listed=listed["name"], cell=cell, stem=stem, name=metrics[-1]["name"]))
    manifest["per_layer"] += [{**meta, "workloads": [cell]} for meta in metrics]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(manifest, fh, indent=1)
    return SimpleNamespace(
        root=str(root), base=base, before=before, added=added, config=name, cell=cell,
        kernel_metric=metrics[1]["name"], metrics=[meta["name"] for meta in metrics],
        same_readings={meta["name"]: meta["name"][:-len(name) - 1] for meta in metrics[2:]})


@pytest.fixture(scope="session")
def widened(tmp_path_factory):
    return widen(str(tmp_path_factory.mktemp("widened")))


@pytest.fixture(params=["live", "widened"])
def manifest_root(request):
    """The root the manifest under test lives at: this repo's, or the widened copy's."""
    return REPO if request.param == "live" else request.getfixturevalue("widened").root


def _as_run(tmp, recorded, stem="cell.7"):
    """What `driver.result_line` hands a reader, from a recorded trace: the
    table `WorkerRun.finish` writes, the raw trace where the tracer left it."""
    from benchmark.harness import xplane

    raw_dir = tmp / "trace" / (stem + ".rank0") / "plugins" / "profile" / "x"
    raw_dir.mkdir(parents=True)
    raw = raw_dir / "host.xplane.pb"
    with gzip.open(recorded, "rb") as src:
        raw.write_bytes(src.read())
    table = xplane.extract(str(raw))
    (tmp / (stem + ".trace.json")).write_text(json.dumps(table))
    return {"summary": {"trace_table": str(tmp / (stem + ".trace.json"))},
            "device_trace": xplane.Trace(table)}


@pytest.fixture(scope="module")
def named_run(tmp_path_factory):
    return _as_run(tmp_path_factory.mktemp("named"), NAMED)


@pytest.fixture(scope="module")
def unnamed_run(tmp_path_factory):
    return _as_run(tmp_path_factory.mktemp("unnamed"), UNNAMED, stem="old.3")
