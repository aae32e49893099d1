"""`benchmark/harness/step_account.py`: the traced step's device time by owner and class, read off what
the raw trace carries beside `tf_op` (`hlo_category`, `flops`, `bytes_accessed`, `source`, the step's
`Hlo Proto`). The rules on tables made by hand; the reading on the two traces recorded on the chip (PR 24's,
whose program carries names, and PR 22's, which has none); the seven entries PR 53 appended."""

import gzip
import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [REPO, os.path.dirname(os.path.abspath(__file__))]

from benchmark.harness import program_trace as pt  # noqa: E402
from benchmark.harness import step_account as sa  # noqa: E402
from benchmark.harness import xplane  # noqa: E402
from benchmark.harness.manifest import Manifest  # noqa: E402
from benchmark.harness.peaks import peaks_for  # noqa: E402
from widened_manifest import NAMED, UNNAMED, _as_run  # noqa: E402

ENTRIES = ("step.product_ms", "step.product_floor_ms", "step.elementwise_ms", "step.movement_ms",
           "step.unowned_ms", "step.misfiled_ms", "collectives.exposed_min_ms")
# What the walk gives on each recorded trace: instructions of the step's program with the stats,
# `step.device_ms`, and the readings of the seven entries.
RECORDED = {
    "named": (NAMED, 331, 0.139106, {
        "step.product_ms": 0.031046, "step.product_floor_ms": 0.015532586883, "step.elementwise_ms": 0.0465595,
        "step.movement_ms": 0.0309195, "step.unowned_ms": 0.0, "step.misfiled_ms": 0.002259,
        "collectives.exposed_min_ms": 0.0}),
    "unnamed": (UNNAMED, 320, 0.146264, {
        "step.product_ms": 0.034893, "step.product_floor_ms": 0.016570184772, "step.elementwise_ms": 0.051081,
        "step.movement_ms": 0.029686, "step.unowned_ms": 0.007527, "step.misfiled_ms": 0.0,
        "collectives.exposed_min_ms": 0.0}),
}


@pytest.fixture(scope="module", params=sorted(RECORDED))
def recorded(request, tmp_path_factory):
    """(a reader's `run` from one recorded trace, as a chip run's parent holds it; what it should read)."""
    path, *want = RECORDED[request.param]
    run = _as_run(tmp_path_factory.mktemp(request.param), path)
    return {**run, "peaks": peaks_for("TPU v5 lite")}, want


@pytest.fixture(scope="module")
def readers():
    return Manifest().layer_readers()


# ------------------------------------------------------------ rules, by hand
@pytest.mark.parametrize("component, want", [
    ("blocks", "blocks"), ("jvp(blocks)", "blocks"), ("transpose(jvp(blocks))", "blocks"),
    ("jit(step_fn)", None), ("jit(take_along_axis)", None), ("transpose(jvp())", None), ("vmap(jit(f))", None),
    ("while", None), ("body", None), ("closed_call", None), ("checkpoint", None), ("rematted_computation", None),
    ("branch_1_fun", None), ("custom_vjp_call_jaxpr", None), ("shard_map", None), ("pallas_call", None),
    ("bsd,vd->bsv", None), ("my_optimizer_state", "my_optimizer_state"), ("tiles_160of512", "tiles_160of512"),
    ("make_train_step.<locals>.step_fn", None),
])
def test_a_component_is_the_programs_scope_or_jaxs_own(component, want):
    assert sa.program_scope(component) == want


@pytest.mark.parametrize("op_name, want", [
    ("jit(step_fn)/jvp(blocks)/while/body/closed_call/qkv/mul", ("blocks", "qkv")),
    ("jit(step_fn)/transpose(jvp(blocks))/while/body/closed_call/out_mlp/out_mlp/checkpoint/"
     "rematted_computation/bsd,df->bsf/dot_general", ("blocks", "out_mlp", "out_mlp")),
    ("jit(step_fn)/jvp(blocks)/while/body/closed_call/attention/tiles_160of512/flash_fwd/pallas_call",
     ("blocks", "attention", "tiles_160of512", "flash_fwd")),  # a kernel's owner is its `name=`
    ("jit(step_fn)/optimizer/mul", ("optimizer",)),
    ("jit(step_fn)/jvp(loss)/jit(take_along_axis)/gather", ("loss",)),
    # What the scan itself emits: nothing of the program between `body` and the primitive.
    ("jit(step_fn)/jvp(blocks)/while/body/dynamic_update_slice", ("blocks", "scan_carry")),
    ("jit(step_fn)/while/body/dynamic_update_slice", ("scan_carry",)),
    ("jit(step_fn)/transpose(jvp(blocks))/while/body/dynamic_slice", ("blocks", "scan_carry")),
    ("jit(step_fn)/transpose(jvp(blocks))/while/body/checkpoint/add_any", ("blocks", "grad_accumulate")),
    ("jit(step_fn)/transpose(jvp(blocks))/while/body/closed_call/qkv/add_any", ("blocks", "qkv")),
    ("jit(step_fn)/jvp(blocks)/while/body/add", ("blocks",)),  # the loop's counter: `blocks` bare
    ("jit(step_fn)/jvp(blocks)/dynamic_update_slice", ("blocks",)),  # not in a loop's body
    ("jit(step_fn)/transpose(jvp(blocks))/while", ("blocks", "scan_carry")),  # the loop's own name on a slice of its carry
    ("jit(step_fn)/mul", ()), ("", ()),
])
def test_an_op_names_scopes_outermost_first(op_name, want):
    assert sa.scope_path(op_name) == want


def _inst(key, name, opcode, op_name="", operands=(), nbytes=0, dims=(), called=(), **more):
    return {"id": key, "name": name, "opcode": opcode, "op_name": op_name, "operands": list(operands),
            "bytes": nbytes, "dims": list(dims), "called": list(called), "target": "", **more}


BLOCK = "jit(step_fn)/transpose(jvp(blocks))/while/body/closed_call/"


@pytest.fixture(scope="module")
def by_hand():
    """A step of nine instructions and two fused computations, and the stats a trace would carry."""
    qkv, out_mlp = BLOCK + "qkv/bsd,dcnh->bscnh/dot_general", BLOCK + "out_mlp/reduce_sum"
    module = sa.link({
        1: [_inst(10, "p0", "parameter", nbytes=2048, dims=(8, 128)),
            _inst(11, "p1", "parameter", nbytes=4096, dims=(8, 256)),
            # [8, 128]^T x [8, 256] over the 8 rows: 2 x 128 x 256 x 8 flops, qkv's
            _inst(12, "convolution.1", "convolution", qkv, (10, 11), 65536, (128, 256), kernel_out=1),
            _inst(13, "reduce.1", "reduce", out_mlp, (12,), 1024, (256,))],
        2: [_inst(20, "q0", "parameter", nbytes=512),
            _inst(21, "convert.1", "convert", BLOCK + "attn_out/convert_element_type", (20,), 1024),
            _inst(22, "slice.1", "dynamic-slice", "jit(step_fn)/jvp(blocks)/while/body/dynamic_slice", (20,), 4096)],
        3: [_inst(30, "x", "parameter", nbytes=2048, dims=(8, 128)),
            _inst(31, "w", "parameter", nbytes=4096, dims=(8, 256)),
            _inst(32, "fusion.7", "fusion", out_mlp, (37, 31), 1024, called=(1,)),
            _inst(33, "fusion.8", "fusion", BLOCK + "attn_out/mul", (30,), 1024, called=(2,)),
            _inst(34, "copy-start.1", "copy-start", "", (32,), 1032),
            _inst(35, "copy-done.1", "copy-done", "", (34,), 1024),
            _inst(36, "dynamic_update_slice.3", "dynamic-update-slice",
                  "jit(step_fn)/jvp(blocks)/while/body/dynamic_update_slice", (31, 35), 4096),
            _inst(37, "copy.9", "copy", "", (30,), 2048),
            _inst(38, "topk.1", "custom-call", "jit(step_fn)/jvp(blocks)/while/body/closed_call/router/top_k",
                  (30,), 64)],
    })
    module["by_name"]["topk.1"]["target"] = "TopK"
    stats = {name: {"op_name": module["by_name"][name]["op_name"], "category": category, "flops": flops,
                    "bytes": 1024, "source": "ray_tpu/models/gpt.py:1"}
             for name, category, flops in [
                 ("fusion.7", "convolution fusion", 524288), ("fusion.8", "loop fusion", 0),
                 ("copy-start.1", "copy-start", 0), ("copy-done.1", "copy-done", 0),
                 ("dynamic_update_slice.3", "dynamic-update-slice", 0), ("copy.9", "data formatting", 0),
                 ("topk.1", "custom-call", 0)]}
    return sa.Owners({"stats": stats, "module": module})


@pytest.mark.parametrize("name, owner, rule, misfiled, klass", [
    # Rooted in `out_mlp` by its reduce; its product, and so its work, is `qkv`'s.
    ("fusion.7", "qkv", "work", True, "product"),
    # A slice inside a fusion is where its user reads: the convert is the work, and the root's scope has it.
    ("fusion.8", "attn_out", "name", False, "movement"),
    ("copy-start.1", "qkv", "operand", False, "movement"),
    ("copy-done.1", "qkv", "operand", False, "movement"),  # through the copy-start to the fusion's work
    ("dynamic_update_slice.3", "scan_carry", "scan", False, "movement"),
    ("copy.9", "qkv", "user", False, "movement"),  # a parameter's copy: no producer with an owner, so its user's
    ("topk.1", "router", "name", False, "unclassed"),  # a custom call this reader's dict does not know
])
def test_owner_rule_misfiling_and_class_on_a_table_made_by_hand(by_hand, name, owner, rule, misfiled, klass):
    inst = by_hand.module["by_name"][name]
    assert (by_hand.owner(name), by_hand.path(name)[1], by_hand.misfiled(name)) == (owner, rule, misfiled)
    assert by_hand.klass(name, inst["opcode"], inst["target"]) == klass


def test_an_operation_nothing_names_is_unowned_and_a_category_nobody_listed_unclassed():
    module = sa.link({1: [_inst(1, "p", "parameter", nbytes=8), _inst(2, "copy.1", "copy", "", (1,), 8),
                          _inst(3, "fusion.1", "fusion", "jit(step_fn)/mul", (2,), 8, called=(2,))],
                      2: [_inst(4, "q", "parameter"), _inst(5, "mul.1", "multiply", "jit(step_fn)/mul", (4, 4), 8)]})
    owners = sa.Owners({"module": module, "stats": {
        "copy.1": {"op_name": "", "category": "data formatting", "flops": 0, "bytes": 16, "source": ""},
        "fusion.1": {"op_name": "jit(step_fn)/mul", "category": "a fusion of next year's", "flops": 8, "bytes": 16,
                     "source": "ray_tpu/models/training.py:99"}}})
    assert [owners.owner(n) for n in ("copy.1", "fusion.1")] == [sa.UNOWNED, sa.UNOWNED]
    assert owners.path("copy.1")[1] == "none" and not owners.misfiled("fusion.1")
    assert owners.klass("fusion.1", "fusion") == "unclassed" and owners.klass("copy.1", "copy") == "movement"
    assert owners.klass("gone.1", "all-gather-start") == "collective"  # by the trace's own opcode, stats or none


@pytest.mark.parametrize("wrapped, klass", [("slice", "movement"), ("all-gather", "collective")])
def test_an_asynchronous_pair_is_a_collective_only_where_what_it_wraps_is_one(wrapped, klass):
    """libtpu files a slice of a loop's carry made asynchronous under `async-start` / `async-done` as it does
    a collective made so (`gpt2-medium.fed`: 1.67 ms a step of `slice-done`s, on one chip)."""
    module = sa.link({
        1: [_inst(10, "p", "parameter", nbytes=64), _inst(11, "inner.1", wrapped, "", (10,), 64)],
        2: [_inst(20, "x", "parameter", nbytes=64),
            _inst(21, "slice-start.1", "async-start", "jit(step_fn)/jvp(blocks)/while", (20,), 64, called=(1,)),
            _inst(22, "slice-done.1", "async-done", "jit(step_fn)/jvp(blocks)/while", (21,), 64)]})
    stat = {"op_name": "jit(step_fn)/jvp(blocks)/while", "flops": 0, "bytes": 64, "source": ""}
    owners = sa.Owners({"module": module, "stats": {"slice-start.1": {**stat, "category": "async-start"},
                                                    "slice-done.1": {**stat, "category": "async-done"}}})
    assert [owners.klass(n, o) for n, o in (("slice-start.1", "async-start"), ("slice-done.1", "async-done"))] == [klass] * 2
    assert owners.owner("slice-done.1") == sa.SCAN_CARRY and owners.path("slice-done.1")[1] == "scan"


def test_an_instruction_whose_id_is_0_is_not_on_the_wire_and_still_found():
    """proto3 leaves a field at its default off the wire: the instruction with id 0 (SDAR's step has one that
    others use) must be found by that id, and a computation with id 0 too."""
    first = bytes([0x0A, 1]) + b"a" + bytes([0x12, 9]) + b"parameter"  # name=1, opcode=2, no id=35
    second = bytes([0x0A, 1]) + b"b" + bytes([0x12, 4]) + b"copy" + bytes([0x98, 0x02, 7, 0xA0, 0x02, 0])  # id=35: 7, operand_ids=36: 0
    computation = bytes([0x12, len(first)]) + first + bytes([0x12, len(second)]) + second  # instructions=2, no id=5
    module = bytes([0x1A, len(computation)]) + computation  # computations=3
    read = sa.read_module(bytes([0x0A, len(module)]) + module)  # hlo_module=1
    assert set(read["computations"]) == {0} and set(read["by_id"]) == {0, 7}
    assert read["by_name"]["b"]["operands"] == [0] and read["by_name"]["a"]["users"] == [7]


def test_a_products_flops_are_its_result_times_what_it_contracts(by_hand):
    by_id = by_hand.module["by_id"]
    assert sa._product_flops(by_id[12], by_id) == 2 * 128 * 256 * 8
    dot = _inst(40, "dot.1", "dot", "", (10, 11), 0, (128, 256), contracting=[0])
    assert sa._product_flops(dot, by_id) == 2 * 128 * 256 * 8


def test_the_wire_readers_take_packed_and_plain_integers_and_a_tuples_leaves():
    assert sa._ints(2, bytes([3, 0x96, 0x01])) == [3, 150] and sa._ints(0, 7) == [7]
    leaf = bytes([0x10, 16, 0x1A, 2, 4, 8])  # element_type=2: BF16, dimensions=3 packed [4, 8]
    assert sa._shape(leaf) == (64, [4, 8])
    f32 = bytes([0x10, 11, 0x18, 2])  # dimensions one varint a field
    assert sa._shape(f32) == (8, [2])
    both = bytes([0x10, 13, 0x22, len(leaf)]) + leaf + bytes([0x22, len(f32)]) + f32
    assert sa._shape(both) == (72, [4, 8])


# ------------------------------------------------- the two recorded traces
def test_every_instruction_that_ran_carries_category_flops_and_bytes(recorded):
    run, (instructions, _, _) = recorded
    raw = sa.read(pt.raw_trace_path(run))
    assert len(raw["stats"]) == instructions
    trace = run["device_trace"]
    ran = {op[0] for op in trace._leaf_ops(trace.devices[0])}
    assert ran and ran <= set(raw["stats"])
    for name in ran:
        stat = raw["stats"][name]
        assert isinstance(stat["category"], str)
        assert stat["category"] in sa.CLASS_OF_CATEGORY or stat["category"] == "custom-call"  # that one by its target
        assert isinstance(stat["flops"], int) and isinstance(stat["bytes"], int) and stat["bytes"] >= 0
    assert sum(s["flops"] > 0 for s in raw["stats"].values()) >= 100
    sources = [s["source"] for s in raw["stats"].values() if s["source"]]
    assert len(sources) > 100 and sum(s.startswith("ray_tpu/") for s in sources) > 100


def test_the_hlo_proto_walk_finds_the_steps_computations_and_fusions(recorded):
    run, _ = recorded
    module = sa.read(pt.raw_trace_path(run))["module"]
    fusions = [i for i in module["by_id"].values() if i["opcode"] == "fusion"]
    assert (len(module["computations"]), len(fusions)) == (216, 156)
    assert all(len(f["called"]) == 1 and f["called"][0] in module["computations"] for f in fusions)
    assert len(module["by_name"]) == len(module["by_id"])  # an instruction's name is its own in a module
    trace = run["device_trace"]
    assert {op[0] for op in trace.devices[0]["ops"]} <= set(module["by_name"])
    kernels = [i for i in module["by_id"].values() if i["target"] == xplane.MOSAIC_TARGET]
    assert len(kernels) == 2


def test_the_classes_add_up_to_the_steps_device_time_and_none_is_unclassed(recorded):
    run, (_, device_ms, _) = recorded
    account = sa.of(run)
    assert account.unknown == [] and account.classes["unclassed"] == 0.0
    assert set(account.classes) == set(sa.CLASSES)
    assert run["device_trace"].step_device_ms() == pytest.approx(device_ms)
    assert sum(account.classes.values()) == pytest.approx(device_ms, abs=1e-9)
    assert account.classes["kernel"] == pytest.approx(run["device_trace"].mosaic_ms(), rel=1e-3)
    assert account.classes["collective"] == 0.0


def test_the_account_is_kept_on_the_run_and_its_table_in_the_summary(recorded, capsys):
    run, _ = recorded
    run = {k: v for k, v in run.items() if k not in ("step_account", "rank_account")}
    run["summary"] = dict(run["summary"])
    account = sa.of(run)
    out = capsys.readouterr().out
    assert account is sa.of(run) and capsys.readouterr().out == ""
    kept = json.loads(json.dumps(run["summary"]["step_account"]))  # it goes into `out/<cell>.<seed>.json`
    assert kept["classes"] == account.classes and 0 < len(kept["rows"]) <= sa.ROWS and kept["reader_s"] < 10
    assert "XLA's count, not a floor" in out and out.count("[run]") == len(kept["rows"]) + 2
    rows = account.rows()
    assert rows == sorted(rows, key=lambda r: -r["ms"])
    assert all(set(r["rules"]) <= set(sa.RULES) and len(r["names"]) <= 3 for r in rows)
    by_source = account.rows("source")
    assert sum(r["ms"] for r in by_source) == pytest.approx(sum(r["ms"] for r in rows))
    assert any(r["source"].startswith("ray_tpu/models/") for r in by_source)


@pytest.mark.parametrize("entry", ENTRIES)
def test_an_entry_reads_the_recorded_trace(recorded, readers, entry):
    run, (_, _, want) = recorded
    assert readers[entry].read(run) == pytest.approx(want[entry], rel=1e-6, abs=1e-12)


def test_what_the_named_trace_says_of_three_operations(tmp_path):
    run = {**_as_run(tmp_path, NAMED), "peaks": peaks_for("TPU v5 lite")}
    owners = sa.of(run).owners
    # Filed under `out_mlp` by its root, a reduce; a convolution of `out_mlp` is its work: not misfiled.
    assert (owners.owner("convert_reduce_fusion.14"), owners.misfiled("convert_reduce_fusion.14")) == ("out_mlp", False)
    assert sa.of(run).klass["convert_reduce_fusion.14"] == "product"
    # Rooted in a block's `out_mlp/.../reduce_sum`; all but 256 of its bytes are the head's backward pass.
    assert (owners.owner("fusion.308"), owners.path("fusion.308")[1], owners.misfiled("fusion.308")) == (
        "head", "work", True)
    # A copy with no `op_name`, into VMEM for the update that reads it.
    assert owners.path("copy-done.4")[:2] == (("optimizer",), "user")


def test_a_program_without_scopes_has_no_owner_but_the_scans_own_and_one_with_scopes_has_them(recorded):
    """No list of the models' scopes in the reader: PR 22's program names nothing, so nothing but what the
    scan itself emits can own an operation; PR 24's carries `stack.py`'s and `gpt.py`'s names, all found."""
    run, (instructions, _, want) = recorded
    account = sa.of(run)
    owners = {account.owners.owner(op[0]) for op in account.ops}
    if instructions == 320:
        assert owners == {sa.SCAN_CARRY, sa.GRAD_ACCUMULATE, sa.UNOWNED}
    else:
        assert owners >= {"embed", "qkv", "flash_fwd", "flash_bwd", "out_mlp", "head", "loss", "optimizer", sa.SCAN_CARRY}
        assert sa.UNOWNED not in owners and want["step.unowned_ms"] == 0.0


# -------------------------------------------- nothing to read; other ranks
def _xspace_of_a_host_alone(path):
    name = b"/host:CPU"
    plane = bytes([0x12, len(name)]) + name  # XPlane.name=2
    path.write_bytes(bytes([0x0A, len(plane)]) + plane)  # XSpace.planes=1


@pytest.mark.parametrize("entry", ENTRIES)
def test_an_entry_reads_nothing_untraced_nor_on_a_cpu_rehearsals_trace(tmp_path, readers, entry):
    untraced = {"summary": {"trace_table": None}, "device_trace": None, "peaks": peaks_for("TPU v5 lite")}
    assert readers[entry].read(untraced) is None and "step_account" not in untraced["summary"]
    raw = tmp_path / "trace" / "cell.7.rank0" / "plugins" / "profile" / "x"
    raw.mkdir(parents=True)
    _xspace_of_a_host_alone(raw / "host.xplane.pb")
    assert sa.read(str(raw / "host.xplane.pb")) is None
    table = {"devices": [], "annotations": [["bench.step", 0.0, 10.0, 0]], "enqueued": {}, "completed": {}}
    for peaks in (None, peaks_for("TPU v5 lite")):  # a rehearsal has no peaks; nor does a trace of the host alone read
        rehearsal = {"summary": {"trace_table": str(tmp_path / "cell.7.trace.json")},
                     "device_trace": xplane.Trace(table), "peaks": peaks}
        assert readers[entry].read(rehearsal) is None and "step_account" not in rehearsal["summary"]


def test_the_other_ranks_are_read_as_rank_0_is_each_on_its_own_clock(tmp_path, readers, capsys):
    run = {**_as_run(tmp_path, NAMED), "peaks": peaks_for("TPU v5 lite")}
    first = pt.raw_trace_path(run)
    for rank, recorded_trace in ((1, UNNAMED), (2, NAMED)):
        there = os.path.dirname(first).replace(".rank0", f".rank{rank}")
        os.makedirs(there)
        with gzip.open(recorded_trace, "rb") as src, open(os.path.join(there, "host.xplane.pb"), "wb") as dst:
            shutil.copyfileobj(src, dst)
    assert [os.sep + f"cell.7.rank{n}" + os.sep in p for n, p in enumerate(sa.rank_paths(run), 1)] == [True, True]
    ranks = sa.ranks(run)
    assert [r["step.device_ms"] for r in ranks] == pytest.approx([0.139106, 0.146264, 0.139106])
    assert [r["collectives.exposed_ms"] for r in ranks] == [0.0, 0.0, 0.0]
    assert readers["collectives.exposed_min_ms"].read(run) == 0.0
    out = capsys.readouterr().out
    assert out.count("[run] ranks 0..2, each on its own clock") == 1 and "step.device_ms [0.139106, 0.146264" in out
    assert json.loads(json.dumps(run["summary"]["rank_account"])) == ranks
    ranks[1]["collectives.exposed_ms"] = 3.5  # the least over the ranks, whichever has it
    ranks[0]["collectives.exposed_ms"], ranks[2]["collectives.exposed_ms"] = 7.0, None
    assert readers["collectives.exposed_min_ms"].read(run) == 3.5


# ------------------------------------------------------------- the manifest
def test_the_seven_entries_are_appended_after_the_74_each_with_its_one_file(readers):
    """74 when PR 53 appended them; 66 since PR 56 folded the eight copies that stood before them."""
    m = Manifest()
    entries = m.data["per_layer"]
    first = [e["name"] for e in entries].index(ENTRIES[0])
    assert first >= 66 and [e["name"] for e in entries[first:first + 7]] == list(ENTRIES)
    files = {os.path.basename(r.__file__) for name, r in readers.items() if name in ENTRIES}
    assert files == {name.replace(".", "_") + ".py" for name in ENTRIES}
    for e in entries[first:first + 7]:
        # No `workloads`: every traced cell, as `step.device_ms`. But the ranks' least exposed time, which read
        # 0.0 where one chip is the gang: it lists four-chip cells since PR 56, the first of them GPT-2's.
        assert readers[e["name"]].META == {k: v for k, v in e.items() if k != "workloads"}
        if e["name"] == "collectives.exposed_min_ms":
            assert e["workloads"][0] == "gpt2-xl-fsdp4.fed" and all(m.cell(c)["chips"] == 4 for c in e["workloads"])
        else:
            assert "workloads" not in e
        assert (e["unit"], e["better"], e["source"], e["moves"]) == (
            "ms/step", "lower", "device_trace", "tokens_per_s_per_chip")
        assert e["layer"] == ("collectives" if e["name"].startswith("collectives.") else "step")
        # Milliseconds only: no share of a roofline or of a peak, and nothing divides by XLA's byte count.
        assert "roofline" not in e["name"] and "mfu" not in e["name"]
        with open(readers[e["name"]].__file__) as fh:
            assert "bytes" not in fh.read().split('"""')[2]
