"""The reduction from a profiler trace to metrics, on a trace recorded on the
chip (PR 22's first chip call: a 2-layer, 128-wide GPT through
`make_train_step`, batch 4 x 256, three steps under `bench.*` annotations, one
TPU v5 lite), with the expected numbers worked out from its events by hand."""

import gzip
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import xplane  # noqa: E402

RECORDED = os.path.join(REPO, "benchmark", "testdata", "tiny_gpt_3_steps_v5e.xplane.pb.gz")


@pytest.fixture(scope="module")
def table(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "tiny.xplane.pb"
    with gzip.open(RECORDED, "rb") as src, open(path, "wb") as dst:
        dst.write(src.read())
    return xplane.extract(str(path))


@pytest.fixture(scope="module")
def trace(table):
    return xplane.Trace(table)


def test_intervals():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8), (4, 4)]) == [(0, 3), (5, 8)]
    assert xplane.measure([(0, 2), (1, 3), (10, 11)]) == 4
    assert xplane.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert xplane.subtract([(0, 4), (6, 8)], []) == [(0, 4), (6, 8)]


def test_instruction_text():
    mosaic = ('%closed_call.28 = (bf16[8,256,64]{2,1,0:T(8,128)(2,1)}, f32[8,256,1]{2,1,0}) '
              'custom-call(bf16[8,256,64]{2,1,0} %bitcast.418), custom_call_target="tpu_custom_call"')
    assert xplane.parse_op(mosaic) == ("closed_call.28", "custom-call", "tpu_custom_call")
    assert xplane.result_type(mosaic) == "bf16[8,256,64]"
    loop = "%while.5 = (s32[]{:T(128)}, /*index=1*/bf16[4,256,128]{1,2,0}) while((s32[], bf16[4,256,128]) %tuple.1), body=%b"
    assert xplane.parse_op(loop)[:2] == ("while.5", "while")
    assert xplane.parse_op("%all-gather-start.3 = f32[8]{0} all-gather-start(f32[2]{0} %p)")[1] == "all-gather-start"
    assert xplane.is_collective("all-gather-start") and xplane.is_collective("all-reduce")
    assert not xplane.is_collective("fusion")


def test_what_the_recorded_trace_holds(table):
    (dev,) = table["devices"]
    assert dev["name"] == "/device:TPU:0"
    assert [(m[0], m[1], m[2], m[3]) for m in dev["modules"]] == [
        ("jit_step_fn", 11, 40990808.0, 157280.0),
        ("jit_step_fn", 12, 46078548.0, 157306.0),
        ("jit_step_fn", 13, 50507900.0, 157152.0)]
    assert len(dev["ops"]) == 1230 and sum(op[1] == "while" for op in dev["ops"]) == 6
    assert sum(op[2] == "tpu_custom_call" for op in dev["ops"]) == 12  # 2 layers x (fwd, bwd) x 3
    steps = [a for a in table["annotations"] if a[0] == "bench.step"]
    assert [a[3] for a in steps] == [0, 1, 2]
    assert {a[0] for a in table["annotations"]} == {
        "bench.step", "bench.dispatch", "bench.sync", "bench.report"}
    assert table["enqueued"] == {"11": 42243053.0, "12": 47472403.0, "13": 51867182.0}


def test_device_clock_is_moved_onto_the_hosts(trace):
    # enqueue - start: 1,252,245 / 1,393,855 / 1,359,282, the device's clock
    # is at least 1,393,855 behind; complete - end: 1,881,055 / 1,879,729 /
    # 1,908,580, at most 1,879,729. The middle.
    assert trace.offset_ns == (1393855.0 + 1879729.0) / 2 == 1636792.0
    assert trace.devices[0]["modules"][0][2] == 40990808.0 + 1636792.0


def test_busy_union_idle_share_and_window(trace):
    # Host steps: 41,519,264 .. 51,146,373 + 4,379,350.
    assert trace.window == (41519264.0, 55525723.0) and trace.host_steps == 3
    assert trace.window_s == pytest.approx(0.014006459)
    # The union of the ops, `while`s left out, per run: 146,045 + 146,340 + 146,264 ns
    # (the durations add up to 223,492 in the first run alone: copies overlap compute).
    assert trace.busy_s == pytest.approx(438649e-9)
    assert 1 - trace.busy_s / trace.window_s == pytest.approx(0.96868, abs=1e-5)


def test_steps_are_the_runs_of_the_step_program(trace):
    dev = trace.devices[0]
    assert [m[1] for m in trace.step_runs(dev)] == [11, 12, 13]
    assert trace.per_step(dev, lambda op: True) == [146045.0, 146340.0, 146264.0]
    assert trace.step_device_ms() == pytest.approx(0.146264)


def test_mosaic_calls_are_found_and_summed_per_step(trace):
    # Four calls per run (12 in the trace, checked above): forward 5,693 + 5,694, fused backward 9,607 + 9,608 = 30,602;
    # then 30,606 and 30,604.
    assert trace.mosaic_ms() == pytest.approx(30604e-6)


def test_idle_gaps_are_named_by_the_host_span_that_covers_most_of_them(trace):
    idle = dict(trace.idle_by_host_span())
    # Before the first op (41,519,264 -> 42,628,398 on the host's clock) the
    # host was in bench.dispatch for 735,449 ns and in bench.sync for 366,743.
    assert idle["bench.dispatch"] == pytest.approx(1109134e-9)
    assert idle["bench.report"] > idle["bench.dispatch"] > idle["bench.sync"]
    assert sum(idle.values()) == pytest.approx(trace.window_s - trace.busy_s)
    assert trace.attribute((0.0, 10.0)) == "unattributed"


def test_top_ops_lead_with_the_fused_backward_kernel(trace):
    (name, seconds), *_ = trace.top_ops(3)
    assert name == "closed_call.29 tpu_custom_call bf16[8,256,64]"
    assert seconds == pytest.approx((9607 + 9608 + 9608 + 9608 + 9608 + 9608) * 1e-9)


def test_collectives_total_and_exposed_on_a_table_made_by_hand():
    """One step of 100 ns: an all-reduce 10..30 beside a fusion 20..50, and an
    asynchronous all-gather 60..90 (its start and done are instants) beside a
    fusion 70..80. In flight 20 + 30; exposed 10 + 20."""
    op = lambda name, opcode, start, dur: [name, opcode, "", "", float(start), float(dur)]
    table = {
        "devices": [{"name": "/device:TPU:0", "modules": [["jit_step_fn", 1, 0.0, 100.0]],
                     "ops": [op("all-reduce.1", "all-reduce", 10, 20), op("fusion.1", "fusion", 20, 30),
                             op("all-gather-start.1", "all-gather-start", 60, 1),
                             op("fusion.2", "fusion", 70, 10),
                             op("all-gather-done.1", "all-gather-done", 89, 1),
                             op("while.1", "while", 0, 100)],
                     "async": [["all-gather-start.1", "all-gather-start", 60.0, 30.0],
                               ["copy-start.1", "copy-start", 0.0, 100.0]]}],
        "annotations": [["bench.step", 0.0, 100.0, 0]], "enqueued": {}, "completed": {},
    }
    trace = xplane.Trace(table)
    assert trace.offset_ns == 0.0
    assert trace.collectives_ms() == (pytest.approx(50e-6), pytest.approx(30e-6))
    # busy: 10..50, 60..61, 70..80, 89..90; the while is not counted.
    assert trace.busy_s == pytest.approx(52e-9)
