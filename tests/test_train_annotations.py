"""`ray_tpu.util.tracing.annotate` and the seven seams of the train path it
names: on the profiler's clock while a `jax.profiler` session runs (a CPU
session records them on `/host:CPU` with their stats, so no chip is needed),
a child span of PR 14's timeline while that is recording, and nothing at all,
not even an import of jax, otherwise."""

import glob
import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

import ray_tpu
import ray_tpu.data
from ray_tpu.util import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEAMS = (
    "ray_tpu.train.report", "ray_tpu.train.report.put", "ray_tpu.data.next_bundle",
    "ray_tpu.data.fetch_block", "ray_tpu.data.slice_batch", "ray_tpu.train.shard_batch",
    "ray_tpu.parallel.host_local_to_global")


@pytest.fixture(autouse=True)
def _tracing_off_after():
    yield
    tracing._enabled = False
    os.environ.pop("RAY_TPU_TRACING", None)


def _run_session(train_fn):
    """A `_TrainSession` driven the way the worker actor drives it; returns
    the reports' metrics."""
    from ray_tpu.train._internal.session import DONE, REPORT, SessionArgs, _TrainSession

    session = _TrainSession(SessionArgs(
        train_fn=train_fn, config={}, world_rank=0, world_size=1, local_rank=0,
        local_world_size=1, node_rank=0))
    session.start()
    reports = []
    while True:
        result = session.next_result(timeout=60)
        if result.type != REPORT:
            assert result.type == DONE, result.error
            return reports
        reports.append(result.metrics)


def test_the_data_path_and_the_report_never_import_jax():
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {REPO!r})
        import ray_tpu, ray_tpu.data
        from ray_tpu.air import session
        sys.path.insert(0, {os.path.join(REPO, "tests")!r})
        from test_train_annotations import _run_session
        assert "jax" not in sys.modules, "the test module itself must not import jax"
        ray_tpu.init(num_cpus=2)
        (shard,) = ray_tpu.data.from_items([{{"x": i}} for i in range(32)]).streaming_split(1)

        def loop(config):
            for batch in shard.iter_batches(batch_size=8):
                session.report({{"rows": len(batch["x"])}})

        reports = _run_session(loop)
        ray_tpu.shutdown()
        assert [r["rows"] for r in reports] == [8, 8, 8, 8], reports
        print("JAX_IMPORTED", "jax" in sys.modules)
    """)
    env = {k: v for k, v in os.environ.items() if k != "RAY_TPU_TRACING"}
    proc = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                          text=True, timeout=120, cwd=os.path.dirname(REPO))
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-3000:]
    assert "JAX_IMPORTED False" in proc.stdout


@pytest.fixture(scope="module")
def traced_events(tmp_path_factory):
    """One CPU profiler session over a loop that crosses every seam; the
    `ray_tpu.*` events of every thread: (name, start, end, stats, thread)."""
    import jax
    from jax.profiler import ProfileData

    from ray_tpu.air import session
    from ray_tpu.air.checkpoint import Checkpoint
    from ray_tpu.models import shard_batch
    from ray_tpu.parallel import MeshSpec, batch_spec, host_local_to_global

    ray_tpu.init(num_cpus=4)
    try:
        (shard,) = ray_tpu.data.from_items(
            [{"tokens": np.full(16, i, np.int32)} for i in range(40)]).streaming_split(1)
        mesh = MeshSpec(data=1).build(jax.devices()[:1])

        def loop(config):
            for i, batch in enumerate(shard.iter_batches(batch_size=6, drop_last=True)):
                placed = shard_batch({"tokens": batch["tokens"]}, mesh)
                host_local_to_global(mesh, batch_spec(), batch["tokens"])
                ckpt = Checkpoint.from_dict({"step": i}) if i == 1 else None
                session.report({"sum": int(placed["tokens"].sum())}, checkpoint=ckpt)

        directory = str(tmp_path_factory.mktemp("cpu_trace"))
        jax.profiler.start_trace(directory)
        try:
            reports = _run_session(loop)
        finally:
            jax.profiler.stop_trace()
    finally:
        ray_tpu.shutdown()
    assert len(reports) == 6
    (path,) = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("ray_tpu."):
                    events.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                                   dict(ev.stats), line.name))
    return sorted(events, key=lambda e: e[1]), path


def test_a_profiler_session_sees_all_seven_seams(traced_events):
    events, _ = traced_events
    count = {name: sum(e[0] == name for e in events) for name in SEAMS}
    assert set(e[0] for e in events) == set(SEAMS)
    # 40 rows in batches of 6: six batches, six reports, six placements.
    assert count["ray_tpu.train.report"] == count["ray_tpu.train.report.put"] == 6
    assert count["ray_tpu.data.slice_batch"] == count["ray_tpu.train.shard_batch"] == 6
    assert count["ray_tpu.parallel.host_local_to_global"] == 6
    # Every block is asked for and then fetched; the last ask finds the stream over.
    assert count["ray_tpu.data.next_bundle"] == count["ray_tpu.data.fetch_block"] + 1 >= 2
    assert len({e[4] for e in events}) == 1  # all on the session's thread


def test_the_put_lies_inside_its_report(traced_events):
    events, _ = traced_events
    reports = [e for e in events if e[0] == "ray_tpu.train.report"]
    puts = [e for e in events if e[0] == "ray_tpu.train.report.put"]
    for report, put in zip(reports, puts):
        assert report[1] <= put[1] and put[2] <= report[2]
    # No seam of the data path or the placement is open while a report is.
    for e in events:
        if not e[0].startswith("ray_tpu.train.report"):
            assert not any(r[1] < e[2] and e[1] < r[2] for r in reports), e


def test_the_seams_carry_their_stats(traced_events):
    events, _ = traced_events
    by_name = {}
    for e in events:
        by_name.setdefault(e[0], []).append(e[3])
    assert [s["checkpoint"] for s in by_name["ray_tpu.train.report"]] == [0, 1, 0, 0, 0, 0]
    assert all(s == {"split": 0} for s in by_name["ray_tpu.data.next_bundle"])
    assert all(s == {"split": 0} for s in by_name["ray_tpu.data.fetch_block"])
    for s in by_name["ray_tpu.data.slice_batch"]:
        assert s["rows"] == 6 and s["carry_rows"] >= 6
    want = 6 * 16 * 4  # six rows of sixteen int32
    assert all(s == {"bytes": want} for s in by_name["ray_tpu.train.shard_batch"])
    assert all(s == {"bytes": want} for s in by_name["ray_tpu.parallel.host_local_to_global"])


def test_the_benchmarks_reader_finds_the_same_spans_without_jax(traced_events):
    """`benchmark/harness/program_trace.py` walks the protobuf itself: same
    names, same times (to the profiler's picosecond rounding), same stats."""
    sys.path.insert(0, REPO)
    from benchmark.harness import program_trace

    events, path = traced_events
    spans = program_trace.read_xplane(path)["program_spans"]
    assert [(s[0], s[3]) for s in spans] == [(e[0], e[3]) for e in events]
    for span, event in zip(spans, events):
        assert span[1] == pytest.approx(event[1], abs=1) and span[2] == pytest.approx(
            event[2] - event[1], abs=1)


def test_with_no_session_an_annotation_is_a_no_op_that_passes_errors_through():
    spans_before = len(tracing._buffer)
    with tracing.annotate("ray_tpu.test.idle", rows=3) as a:
        assert a._ctx is None
    with pytest.raises(KeyError):
        with tracing.annotate("ray_tpu.test.raises"):
            raise KeyError("through")
    assert len(tracing._buffer) == spans_before


def test_recording_spans_get_one_child_per_annotation_and_none_without_a_parent():
    tracing.enable(exporter=None)
    before = len(tracing._buffer)
    with tracing.annotate("ray_tpu.test.orphan"):
        pass  # recording, but no span is current on this thread
    assert len(tracing._buffer) == before
    with tracing.span("parent") as parent:
        with tracing.annotate("ray_tpu.test.child", rows=4):
            pass

        def elsewhere():  # another thread has no current span
            with tracing.annotate("ray_tpu.test.other_thread"):
                pass

        t = threading.Thread(target=elsewhere)
        t.start()
        t.join()
    with tracing._lock:
        recorded = tracing._buffer[before:]
        del tracing._buffer[before:]
    children = [s for s in recorded if s["kind"] == "annotation"]
    assert [s["name"] for s in children] == ["ray_tpu.test.child"]
    (child,) = children
    assert child["parent_id"] == parent["span_id"] and child["trace_id"] == parent["trace_id"]
    assert child["attributes"] == {"rows": 4} and child["status"] == "OK"
    assert parent["start"] <= child["start"] <= child["end"] <= parent["end"]
    tracing._enabled = False
    os.environ.pop("RAY_TPU_TRACING", None)
    before = len(tracing._buffer)
    with tracing.span("timeline only"):  # tracing off: a span may still be current
        with tracing.annotate("ray_tpu.test.off"):
            pass
    assert [s for s in tracing._buffer[before:] if s["kind"] == "annotation"] == []
