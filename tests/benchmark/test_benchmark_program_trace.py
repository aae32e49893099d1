"""`benchmark/harness/program_trace.py`: from the names the program gives its
own work to the eight per-layer metrics that read them. The rules on text
made by hand; the reduction on a trace recorded on the chip (PR 24: the
benchmark's own fed loop over a 2-layer, 128-wide GPT, batch 4 x 256, blocks
of 8 rows, eight traced steps on one TPU v5 lite, with the names in), and on
PR 22's recorded trace, which has none."""

import os
import struct
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [REPO, os.path.dirname(os.path.abspath(__file__))]

import listed_readings  # noqa: E402
from benchmark.harness import program_trace as pt  # noqa: E402
from benchmark.harness import xplane  # noqa: E402
from benchmark.harness.manifest import Manifest, problems  # noqa: E402
from widened_manifest import manifest_root, named_run, unnamed_run, widened  # noqa: E402,F401  (fixtures)

TESTDATA = os.path.join(REPO, "benchmark", "testdata")   # `named_run`, `unnamed_run`: widened_manifest.py
NAMED = os.path.join(TESTDATA, "tiny_gpt_named_v5e.xplane.pb.gz")
UNNAMED = os.path.join(TESTDATA, "tiny_gpt_3_steps_v5e.xplane.pb.gz")
NEW_METRICS = {
    "step.forward_ms": None, "step.recompute_ms": None, "step.backward_ms": None,
    "step.optimizer_ms": None, "kernels.flash_fwd_ms": None, "kernels.flash_bwd_ms": None,
    "data.fetch_block_ms": listed_readings.TABLE["data.fetch_block_ms"],  # the cells on the lists: PR 50
    "host.report_put_ms": listed_readings.TABLE["host.report_put_ms"],
}


@pytest.fixture(scope="module")
def named(named_run):
    return pt.ProgramTrace(named_run["device_trace"],
                           pt.read_xplane(pt.raw_trace_path(named_run)))


# ------------------------------------------------------------ rules, by hand
def test_scope_map_reads_tuples_fusions_roots_and_skips_parameters():
    text = "\n".join([
        'HloModule jit_step_fn, entry_computation_layout={(f32[8]{0})->f32[8]{0}}',
        '%fused_computation.3 (param_0.1: bf16[8,128]) -> bf16[8,128] {',
        '  %param_0.1 = bf16[8,128]{1,0} parameter(0), metadata={op_name="state.params[\\\'wte\\\']"}',
        '  ROOT %multiply.7 = bf16[8,128]{1,0} multiply(%param_0.1, %param_0.1), '
        'metadata={op_name="jit(step_fn)/jvp(blocks)/while/body/closed_call/qkv/mul" stack_frame_id=4}',
        '}',
        'ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[8] {',
        '  %fusion.3 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(%x), kind=kLoop, calls=%fused_computation.3, '
        'metadata={op_name="jit(step_fn)/jvp(blocks)/while/body/closed_call/qkv/mul" stack_frame_id=4}',
        '  %flash_bwd.10 = (bf16[8,256,64]{2,1,0}, bf16[8,256,64]{2,1,0}) custom-call(%a, %b), '
        'custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/transpose(jvp(blocks))'
        '/while/body/closed_call/attention/flash_bwd/pallas_call" stack_frame_id=77}',
        '  %copy-start.4 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%y)',
        '  %get-tuple-element.2 = f32[8]{0} get-tuple-element(%t), index=0, metadata={}',
        '  ROOT %add.90 = f32[] add(%u, %v), metadata={op_name="jit(step_fn)/optimizer/add"}',
        '}'])
    assert pt.scope_map(text) == {
        "multiply.7": "jit(step_fn)/jvp(blocks)/while/body/closed_call/qkv/mul",
        "fusion.3": "jit(step_fn)/jvp(blocks)/while/body/closed_call/qkv/mul",
        "flash_bwd.10": "jit(step_fn)/transpose(jvp(blocks))/while/body/closed_call/attention/"
                        "flash_bwd/pallas_call",
        "add.90": "jit(step_fn)/optimizer/add"}


@pytest.mark.parametrize("op_name, want", [
    ("jit(step_fn)/jvp(embed)/gather", "forward"),
    ("jit(step_fn)/jvp(blocks)/while/body/closed_call/attention/flash_fwd/pallas_call", "forward"),
    ("jit(step_fn)/jvp(loss)/jit(take_along_axis)/gather", "forward"),
    ("jit(step_fn)/transpose(jvp(head))/bsd,vd->bsv/dot_general", "backward"),
    ("jit(step_fn)/transpose(jvp(blocks))/while/body/closed_call/qkv/qkv/checkpoint/"
     "rematted_computation/bsd,dcnh->bscnh/dot_general", "recompute"),
    ("jit(step_fn)/transpose(jvp(blocks))/while/body/checkpoint/rematted_computation/qkv/mul", "recompute"),
    ("jit(step_fn)/optimizer/mul", "optimizer"),
    ("jit(step_fn)/grad_norm/sqrt", "optimizer"),
    ("jit(step_fn)/attention/jit(tril)/iota", "other"),   # hoisted out of both passes
    ("jit(step_fn)/my_optimizer_state/add", "other"),     # a scope is a whole path component
    ("", "other"),
])
def test_phase_rules(op_name, want):
    assert pt.phase(op_name) == want


def test_the_wire_reader_on_a_message_made_by_hand():
    inner = b"\x08\x96\x01" + b"\x12\x03abc"                 # 1: varint 150, 2: "abc"
    message = (b"\x0a" + bytes([len(inner)]) + inner          # 1: the message above
               + b"\x11" + struct.pack("<d", 2.5)             # 2: fixed64
               + b"\x18\xff\xff\xff\xff\xff\xff\xff\xff\xff\x01")  # 3: varint 2**64 - 1
    fields = list(pt._fields(memoryview(message)))
    assert [(f, w) for f, w, _ in fields] == [(1, 2), (2, 1), (3, 0)]
    assert [(f, bytes(v) if w == 2 else v) for f, w, v in pt._fields(fields[0][2])] == [
        (1, 150), (2, b"abc")]
    assert struct.unpack("<d", fields[1][2])[0] == 2.5
    assert pt._signed(fields[2][2]) == -1
    with pytest.raises(ValueError, match="wire type"):
        list(pt._fields(memoryview(b"\x0b")))                 # a group: not in an xplane


# ------------------------------------------------- the recorded, named trace
def test_what_the_named_trace_holds(named):
    assert len(named.scopes) == 166 and named.named
    assert named.scopes["flash_fwd.3"] == (
        "jit(step_fn)/jvp(blocks)/while/body/closed_call/attention/flash_fwd/pallas_call")
    assert named.scopes["flash_bwd.10"].endswith("/attention/flash_bwd/pallas_call")
    count = {}
    for name, _, _, _ in named.spans:
        count[name] = count.get(name, 0) + 1
    # Eight steps of 4 rows from blocks of 8: a pull every second step.
    assert count == {
        "ray_tpu.data.next_bundle": 4, "ray_tpu.data.fetch_block": 4,
        "ray_tpu.data.slice_batch": 8, "ray_tpu.train.shard_batch": 8,
        "ray_tpu.train.report": 8, "ray_tpu.train.report.put": 8}
    first = {name: stats for name, _, _, stats in reversed(named.spans)}
    assert first["ray_tpu.data.slice_batch"] == {"rows": 4, "carry_rows": 10}
    assert first["ray_tpu.train.shard_batch"] == {"bytes": 4 * 257 * 4}
    assert first["ray_tpu.train.report"] == {"checkpoint": 0}


def test_both_ways_to_the_host_events_agree(monkeypatch, unnamed_run):
    """jax's `ProfileData` (`xplane.extract`) and this file's own walk over
    the protobuf give the same events at the same times."""
    monkeypatch.setattr(pt, "PROGRAM_PREFIX", xplane.ANNOTATION_PREFIX)
    mine = pt.read_xplane(pt.raw_trace_path(unnamed_run))["program_spans"]
    theirs = unnamed_run["device_trace"].annotations
    assert [s[:3] for s in mine] == [a[:3] for a in theirs] and len(mine) == 12
    assert [s[3].get("step_num", -1) for s in mine] == [a[3] for a in theirs]


def test_the_five_phases_add_up_to_the_steps_device_time(named):
    trace = named.trace
    phases = pt.phase_ms(trace, named.scopes)
    assert phases == {"forward": pytest.approx(0.0449145), "recompute": pytest.approx(0.003698),
                      "backward": pytest.approx(0.0629835), "optimizer": pytest.approx(0.0128765),
                      "other": pytest.approx(0.0145245)}
    # Medians of the parts against the median of the whole: 0.138997 and 0.139106.
    assert sum(phases.values()) == pytest.approx(trace.step_device_ms(), rel=0.01)
    assert trace.step_device_ms() == pytest.approx(0.139106)
    # Step by step the split is exact: no nanosecond is counted twice or lost.
    dev = trace.devices[0]
    whole = trace.per_step(dev, lambda op: True)
    parts = [trace.per_step(dev, lambda op, p=p: pt.phase(named.scopes.get(op[0], "")) == p)
             for p in pt.PHASES]
    assert len(whole) == 8
    for i, total in enumerate(whole):
        assert sum(p[i] for p in parts) >= total            # unions overlap a little
    assert named.phase("forward") == phases["forward"] and named.phase("nonsense") is None


def test_a_nanosecond_two_phases_cover_goes_to_the_first_of_them():
    op = lambda name, start, dur: [name, "fusion", "", "", float(start), float(dur)]
    table = {"devices": [{"name": "/device:TPU:0", "modules": [["jit_step_fn", 1, 0.0, 100.0]],
                          "ops": [op("fwd", 0, 40), op("copy", 30, 30), op("bwd", 50, 30),
                                  op("opt", 90, 10)], "async": []}],
             "annotations": [["bench.step", 0.0, 100.0, 0]], "enqueued": {}, "completed": {}}
    scopes = {"fwd": "jit(step_fn)/jvp(blocks)/mul", "bwd": "jit(step_fn)/transpose(jvp(blocks))/mul",
              "opt": "jit(step_fn)/optimizer/add"}
    phases = pt.phase_ms(xplane.Trace(table), scopes)
    # The unnamed copy 30..60 keeps only 40..50, which no named phase covers.
    assert phases == {"forward": 40e-6, "recompute": 0.0, "backward": 30e-6,
                      "optimizer": 10e-6, "other": 10e-6}
    assert sum(phases.values()) == xplane.Trace(table).step_device_ms() == 90e-6


def test_the_two_kernels_are_told_apart_and_add_up(named):
    fwd = pt.kernel_ms(named.trace, named.scopes, pt.FLASH_FWD)
    bwd = pt.kernel_ms(named.trace, named.scopes, pt.FLASH_BWD)
    # Two layers: forward 2 x 5.69 us, fused backward 2 x 9.60 us a step.
    assert (fwd, bwd) == (pytest.approx(0.0113895), pytest.approx(0.019195))
    assert fwd + bwd == pytest.approx(named.trace.mosaic_ms(), rel=1e-3)
    assert pt.kernel_ms(named.trace, named.scopes, "flash") is None  # a whole component


def test_idle_time_goes_to_the_innermost_program_span(named):
    idle = pt.idle_by_program_span(named.trace, named.spans)
    assert sum(sec for _, sec in idle) == pytest.approx(named.trace.window_s - named.trace.busy_s)
    assert idle[0] == ("bench.data_wait/ray_tpu.data.next_bundle", pytest.approx(0.00842921))
    by_name = dict(idle)
    assert by_name["bench.data_wait/ray_tpu.data.fetch_block"] == pytest.approx(0.002221749)
    # The report is open while the device idles, and the put inside it gets its own share.
    assert "bench.report/ray_tpu.train.report" in by_name or "bench.sync" in by_name
    # By hand: a gap 0..100 under bench.sync, a report 10..60 with its put 20..30 inside.
    table = {"devices": [{"name": "/device:TPU:0", "modules": [], "async": [],
                          "ops": [["f", "fusion", "", "", 100.0, 50.0]]}],
             "annotations": [["bench.step", 0.0, 150.0, 0], ["bench.sync", 0.0, 100.0, -1]],
             "enqueued": {}, "completed": {}}
    spans = [["ray_tpu.train.report", 10.0, 50.0, {}], ["ray_tpu.train.report.put", 20.0, 10.0, {}]]
    assert pt.idle_by_program_span(xplane.Trace(table), spans) == [
        ("bench.sync", pytest.approx(50e-9)), ("bench.sync/ray_tpu.train.report", pytest.approx(40e-9)),
        ("bench.sync/ray_tpu.train.report.put", pytest.approx(10e-9))]


def test_exposed_collectives_are_split_by_the_phase_of_their_scope():
    """One step of 100 ns: an all-gather of the forward pass 0..20 with a
    fusion under its second half, an asynchronous all-reduce of the backward
    pass 50..90 with a fusion 60..70 under it."""
    op = lambda name, opcode, start, dur: [name, opcode, "", "", float(start), float(dur)]
    table = {"devices": [{"name": "/device:TPU:0", "modules": [["jit_step_fn", 1, 0.0, 100.0]],
                          "ops": [op("all-gather.1", "all-gather", 0, 20), op("fusion.1", "fusion", 10, 10),
                                  op("all-reduce-start.2", "all-reduce-start", 50, 1),
                                  op("fusion.2", "fusion", 60, 10),
                                  op("all-reduce-done.2", "all-reduce-done", 89, 1)],
                          "async": [["all-reduce-start.2", "all-reduce-start", 50.0, 40.0]]}],
             "annotations": [["bench.step", 0.0, 100.0, 0]], "enqueued": {}, "completed": {}}
    scopes = {"all-gather.1": "jit(step_fn)/jvp(blocks)/while/body/all_gather",
              "all-reduce-start.2": "jit(step_fn)/transpose(jvp(blocks))/while/body/psum"}
    trace = xplane.Trace(table)
    assert pt.exposed_collectives_ms_by_phase(trace, scopes) == {
        "forward": pytest.approx(10e-6), "backward": pytest.approx(30e-6)}
    assert trace.collectives_ms() == (pytest.approx(60e-6), pytest.approx(40e-6))


# ------------------------------------------------------------- the readers
@pytest.fixture(scope="module")
def readers():
    return Manifest().layer_readers()


def test_the_eight_readers_on_the_named_trace(named_run, readers, capsys):
    named_run = {**named_run, "summary": dict(named_run["summary"])}  # as a fresh `result_line` has it
    got = {name: readers[name].read(named_run) for name in NEW_METRICS}
    assert got == {
        "step.forward_ms": pytest.approx(0.0449145), "step.recompute_ms": pytest.approx(0.003698),
        "step.backward_ms": pytest.approx(0.0629835), "step.optimizer_ms": pytest.approx(0.0128765),
        "kernels.flash_fwd_ms": pytest.approx(0.0113895), "kernels.flash_bwd_ms": pytest.approx(0.019195),
        # Four pulls: 2.18 + 0.53, 2.15 + 0.57, ... ms; the median of the puts' 22-51 us.
        "data.fetch_block_ms": pytest.approx(2.71361), "host.report_put_ms": pytest.approx(0.0312055)}
    assert got["kernels.flash_fwd_ms"] + got["kernels.flash_bwd_ms"] == pytest.approx(
        readers["kernels.flash_ms"].read(named_run), rel=1e-3)
    out = capsys.readouterr().out.splitlines()
    # The lines only this layer can write come once, however many readers ask.
    assert [line.split(" {")[0].split(" [")[0] for line in out] == [
        "[run] idle seconds by program span", "[run] step phases ms/step"]
    assert named_run["summary"]["idle_by_program_span"][0][0] == "bench.data_wait/ray_tpu.data.next_bundle"
    assert set(named_run["summary"]["phase_ms"]) == set(pt.PHASES)


def test_a_program_without_names_gives_nothing_and_raises_nothing(unnamed_run, readers):
    """PR 22's trace is the parent's program: `jvp(` and `transpose(` but no
    scope, no kernel name, no `ray_tpu.*` span. The driver lays these readers
    over the parent's checkout, so each must come back empty-handed."""
    raw = pt.read_xplane(pt.raw_trace_path(unnamed_run))
    assert len(raw["scopes"]) == 168 and not pt.names_the_step(raw["scopes"])
    assert raw["program_spans"] == []
    assert {name: readers[name].read(unnamed_run) for name in NEW_METRICS} == dict.fromkeys(NEW_METRICS)
    untraced = {"summary": {"trace_table": None}, "device_trace": None}
    assert {name: readers[name].read(untraced) for name in NEW_METRICS} == dict.fromkeys(NEW_METRICS)
    assert untraced["program_trace"] is None and "idle_by_program_span" not in untraced["summary"]
    # The fifteen that were there read what they read before.
    assert readers["kernels.flash_ms"].read(unnamed_run) == pytest.approx(30604e-6)
    assert readers["step.device_ms"].read(unnamed_run) == pytest.approx(0.146264)


def test_the_manifests_first_23_entries_are_the_seeds_and_pr_24s_in_their_order(manifest_root):
    """What PR 22 and PR 24 put there stays where it is; what later PRs append follows."""
    m = Manifest(manifest_root)
    assert problems(m) == []
    names = [e["name"] for e in m.data["per_layer"]]
    assert names[:15] == [
        "entry.first_step_s", "compile.backend_s", "data.wait_ms", "host.h2d_ms", "host.report_ms",
        "host.cpu_ms", "host.stall_pct", "step.device_ms", "step.mfu_pct", "kernels.flash_ms",
        "kernels.flash_roofline", "collectives.total_ms", "collectives.exposed_ms", "device.idle_pct",
        "device.step_hbm_gib"]
    assert names[15:23] == list(NEW_METRICS) and len(names) == len(set(names))  # the cap: `widened_manifest.hold_the_room`
    for entry in m.data["per_layer"][15:23]:
        assert entry.get("workloads") == NEW_METRICS[entry["name"]]
        assert entry["moves"] == "tokens_per_s_per_chip" and entry["better"] == "lower"
    assert os.path.getsize(NAMED) + os.path.getsize(UNNAMED) < 1 << 20
