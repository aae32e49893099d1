"""The bring-up timeline and the compile counter (ISSUE 37): the spans a
gang's `fit()` leaves in the goodput ledger's report, what
`jax_process.compile_stats()` counts, the step clock's automatic "compile"
phase, and the report a process keeps past `shutdown()`."""

import time

import pytest

import ray_tpu
from ray_tpu.air import RunConfig, ScalingConfig, session
from ray_tpu.train._internal import ledger
from ray_tpu.train._internal.telemetry import COMPILE_TOTALS, SpanLog, StepClock

FIT = "ray_tpu.train.fit"
DRIVER_SPANS = ("placement", "spawn", "backend", "backend.chip_grant", "backend.start_jax", "session")
RANK_SPANS = ("ray_tpu.train.bringup.spawn.worker", "ray_tpu.train.worker.import_jax",
              "ray_tpu.train.worker.distributed_init", "ray_tpu.train.worker.device_touch",
              "ray_tpu.train.worker.mesh_build", "ray_tpu.train.worker.first_report")
PARENT_OF = {
    "ray_tpu.train.bringup.placement": FIT, "ray_tpu.train.bringup.spawn": FIT,
    "ray_tpu.train.bringup.backend": FIT, "ray_tpu.train.bringup.session": FIT,
    "ray_tpu.train.bringup.spawn.worker": "ray_tpu.train.bringup.spawn",
    "ray_tpu.train.bringup.backend.chip_grant": "ray_tpu.train.bringup.backend",
    "ray_tpu.train.bringup.backend.start_jax": "ray_tpu.train.bringup.backend",
    "ray_tpu.train.worker.import_jax": "ray_tpu.train.bringup.backend.start_jax",
    "ray_tpu.train.worker.distributed_init": "ray_tpu.train.bringup.backend.start_jax",
    "ray_tpu.train.worker.device_touch": "ray_tpu.train.bringup.backend.start_jax",
    "ray_tpu.train.worker.mesh_build": "ray_tpu.train.bringup.session",
    "ray_tpu.train.worker.first_report": "ray_tpu.train.bringup.session",
}


@pytest.fixture(scope="module")
def gang_report(tmp_path_factory):
    """The final report of a two-worker CPU gang (so that `_start_jax` joins
    the gang and touches a device), as published and as kept; read after
    `shutdown()`."""
    from ray_tpu._private.worker import global_worker
    from ray_tpu.train.jax import JaxTrainer
    from ray_tpu.util import state

    def compiling_loop(config):
        """Never marks a phase: compiles in its first step, then steps for nothing."""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def heavy(x):
            for _ in range(40):
                x = jnp.tanh(x @ x) + jnp.sin(x)
            return x.sum()

        for i in range(4):
            session.report({"v": float(heavy(jnp.ones((32, 32)))) if i == 0 else 0.0})

    ray_tpu.init(num_cpus=4)
    try:
        t_fit = time.time()
        result = JaxTrainer(
            compiling_loop, scaling_config=ScalingConfig(num_workers=2),
            run_config=RunConfig(name="bringup", storage_path=str(tmp_path_factory.mktemp("run"))),
        ).fit()
        assert result.error is None
        published = next(iter(state.training_report()["gangs"].values()))
        from ray_tpu.util import tracing

        timeline = [s for s in tracing.collect_spans() if s["kind"] == "bringup"]
    finally:
        ray_tpu.shutdown()
    assert global_worker.context is None
    kept = ledger.kept_reports()[-1]
    # Reading the kept report asked no cluster and started none.
    assert global_worker.context is None and not ray_tpu.is_initialized()
    return {"kept": kept, "published": published, "t_fit": t_fit, "timeline": timeline}


def _named(report, name):
    return [s for s in report["bringup"] if s["name"] == name]


def test_report_holds_every_span_once_a_rank_under_one_trace(gang_report):
    rep = gang_report["kept"]
    assert rep["gang"] == gang_report["published"]["gang"] and rep["status"] == "done"
    (root,) = _named(rep, FIT)
    assert root["parent_id"] is None and root["start"] >= gang_report["t_fit"]
    assert root["attributes"]["world_size"] == 2 and root["attributes"]["attempt"] == 0
    assert {s["trace_id"] for s in rep["bringup"]} == {root["trace_id"]}
    assert len(rep["bringup"]) <= ledger.MAX_BRINGUP_SPANS
    for short in DRIVER_SPANS:
        (span,) = _named(rep, "ray_tpu.train.bringup." + short)
        assert "rank" not in span["attributes"]
    for name in RANK_SPANS:
        assert sorted(s["attributes"]["rank"] for s in _named(rep, name)) == [0, 1], name
    for s in rep["bringup"]:
        assert s["kind"] == "bringup" and s["attributes"]["gang"] == rep["gang"]
        assert s["end"] >= s["start"] and s["status"] == "OK"
    touch = _named(rep, "ray_tpu.train.worker.device_touch")[0]["attributes"]
    assert touch["platform"] == "cpu" and touch["local_devices"] >= 1
    assert "TPU_VISIBLE_CHIPS" in touch
    spawned = _named(rep, "ray_tpu.train.bringup.spawn.worker")[0]["attributes"]
    assert spawned["pid"] > 0 and spawned["exec_s"] >= 0 and spawned["import_s"] > 0
    assert _named(rep, "ray_tpu.train.bringup.placement")[0]["attributes"]["bundles"] == [
        {"CPU": 1.0}, {"CPU": 1.0}]
    assert (_named(rep, "ray_tpu.train.worker.mesh_build")[0]["attributes"]["mesh"]["data"]
            == 2 * touch["local_devices"])
    for short in ("backend", "backend.chip_grant", "backend.start_jax"):
        assert _named(rep, "ray_tpu.train.bringup." + short)[0]["attributes"] == {
            "gang": rep["gang"], "distributed": True, "granted": 0}


def test_children_lie_inside_their_parents_and_the_seams_cover_bring_up(gang_report):
    rep = gang_report["kept"]
    by_id = {s["span_id"]: s for s in rep["bringup"]}
    for s in rep["bringup"]:
        if s["name"] == FIT:
            continue
        parent = by_id[s["parent_id"]]
        assert parent["name"] == PARENT_OF[s["name"]], s["name"]
        # A rank's span is on its own process's clock: the same host's.
        assert parent["start"] - 0.05 <= s["start"], s["name"]
        if s["name"] not in ("ray_tpu.train.worker.mesh_build", "ray_tpu.train.worker.first_report"):
            assert s["end"] <= parent["end"] + 0.05, s["name"]  # the session thread runs on
    (root,) = _named(rep, FIT)
    entered = min(s["start"] for s in _named(rep, "ray_tpu.train.worker.first_report")
                  if s["attributes"]["rank"] == 0)
    (session_span,) = _named(rep, "ray_tpu.train.bringup.session")
    seams = [(s["start"], s["end"]) for n in ("placement", "spawn", "backend")
             for s in _named(rep, "ray_tpu.train.bringup." + n)]
    seams.append((session_span["start"], entered))
    covered, reach = 0.0, root["start"]
    for lo, hi in sorted(seams):
        covered += max(0.0, hi - max(lo, reach))
        reach = max(reach, hi)
    assert covered >= 0.95 * (entered - root["start"])


def test_compile_bucket_fills_for_a_loop_that_never_marks_it(gang_report):
    rep = gang_report["kept"]
    rank0 = rep["compile"]["rank0"]
    assert rank0["traces"] > 0 and rank0["lowerings"] > 0 and rank0["compiles"] > 0
    assert rank0["seconds"] == pytest.approx(
        rank0["trace_s"] + rank0["lower_s"] + rank0["backend_s"])
    assert rank0["functions"]["heavy"]["traces"] == 1
    assert rank0["functions"]["heavy"]["compiles"] == 1
    assert rep["compile"]["gang_max"]["seconds"] >= rank0["seconds"] > 0.05
    # The gang's mean of the counter's seconds (the mesh build, marked by the
    # session itself, is milliseconds), out of `productive`.
    assert rep["buckets"]["compile"] >= 0.5 * rank0["seconds"]
    assert rep["buckets"]["productive"] < 0.5 * rep["buckets"]["compile"]
    assert rep["coverage"] >= 0.95
    # The first report carried the span with the counter's deltas over it.
    first = [s for s in _named(rep, "ray_tpu.train.worker.first_report")
             if s["attributes"]["rank"] == 0][0]["attributes"]
    assert first["compiles"] >= 1 and first["backend_s"] > 0
    joins = [s["end"] - s["start"] for s in _named(rep, "ray_tpu.train.worker.distributed_init")]
    assert rep["buckets"]["rendezvous_wait"] == pytest.approx(sum(joins) / 2, abs=1e-3)
    lines = ledger.bringup_lines(rep)
    assert any("bringup.spawn" in line for line in lines)
    assert any(line.startswith("compile (rank 0):") for line in lines)


def test_the_timeline_shows_the_spans_under_the_roots_trace(gang_report):
    """`enable_timeline` is on by default: the spans were also pushed to the
    head's ring, where `ray_tpu.timeline()` reads (a session flushes its
    spans before its last result)."""
    (root,) = _named(gang_report["kept"], FIT)
    pushed = {s["name"] for s in gang_report["timeline"] if s["trace_id"] == root["trace_id"]}
    assert {FIT, "ray_tpu.train.bringup.spawn", "ray_tpu.train.bringup.spawn.worker",
            "ray_tpu.train.bringup.backend", "ray_tpu.train.bringup.session",
            "ray_tpu.train.worker.import_jax", "ray_tpu.train.worker.first_report"} <= pushed


def test_the_removed_remote_round_is_gone():
    from ray_tpu.train._internal import backend_executor

    assert not hasattr(backend_executor.BackendExecutor, "gang_rendezvous_seconds")
    assert not hasattr(backend_executor, "_rendezvous_wait_total")


def test_the_reports_spans_are_built_once_a_change_and_bounded():
    led = ledger.GoodputLedger("cached", 2)
    root = led.open_root(0)
    with led.spans.span("ray_tpu.train.bringup.placement"):
        pass
    first = led.report()["bringup"]
    assert [s["name"] for s in first] == ["ray_tpu.train.bringup.placement"]
    assert led.report()["bringup"] is first  # nothing finished since: not rebuilt
    for rank in range(40):  # two spans a rank: more than a report holds
        led.note_spans([{"name": "ray_tpu.train.worker.import_jax", "start": 2.0 + rank, "end": 3.0 + rank,
                         "attributes": {"gang": "cached", "rank": rank}},
                        {"name": "ray_tpu.train.worker.device_touch", "start": 3.0 + rank, "end": 4.0 + rank,
                         "attributes": {"gang": "cached", "rank": rank}}])
    led.close_root(root)
    held = led.report()["bringup"]
    assert len(held) == ledger.MAX_BRINGUP_SPANS and led.report()["bringup"] is held
    # The driver's own spans stay; the ranks' go from the highest rank down.
    assert {FIT, "ray_tpu.train.bringup.placement"} <= {s["name"] for s in held}
    assert max(s["attributes"].get("rank", 0) for s in held) == 30


def test_kept_reports_are_bounded_to_the_last_gangs():
    before = list(ledger._KEPT.items())
    try:
        for i in range(ledger._KEPT_GANGS + 2):
            ledger.GoodputLedger(f"bound-{i}", 1).finalize("done")
        kept = ledger.kept_reports()
        assert len(kept) == ledger._KEPT_GANGS
        assert [r["gang"] for r in kept][-1] == f"bound-{ledger._KEPT_GANGS + 1}"
    finally:
        ledger._KEPT.clear()
        ledger._KEPT.update(before)


# ------------------------------------------------------------ compile counter
def test_compile_stats_counts_by_function_name():
    import jax
    import jax.numpy as jnp

    from ray_tpu._private.accelerators import jax_process

    jax_process._count_compiles()
    jax_process._count_compiles()  # once a process, whoever asks again

    @jax.jit
    def counted_by_name(x):
        return (x * 2).sum()

    def row():
        return dict(jax_process.compile_stats()["functions"].get(
            "counted_by_name", {"traces": 0, "lowerings": 0, "compiles": 0}))

    ones, more = jnp.ones((4,)), jnp.ones((8,))  # their own ops counted before `before`
    before = jax_process.compile_stats()
    counted_by_name(ones)
    assert (row()["traces"], row()["lowerings"], row()["compiles"]) == (1, 1, 1)
    counted_by_name(ones)
    assert (row()["traces"], row()["lowerings"], row()["compiles"]) == (1, 1, 1)
    counted_by_name(more)
    assert (row()["traces"], row()["lowerings"], row()["compiles"]) == (2, 2, 2)
    after = jax_process.compile_stats()
    assert after["compiles"] - before["compiles"] == 2
    assert after["traces"] - before["traces"] >= 2  # the ops inside are jitted too
    assert after["seconds"] - before["seconds"] == pytest.approx(
        sum(after[k] - before[k] for k in ("trace_s", "lower_s", "backend_s")))
    assert row()["trace_s"] > 0 and row()["lower_s"] > 0 and row()["backend_s"] > 0
    assert len(after["functions"]) <= jax_process._FUNCTIONS_SHOWN + 1


def test_function_rows_are_bounded_and_the_rest_is_other():
    from ray_tpu._private.accelerators import jax_process

    kept = dict(jax_process._BY_FUNCTION)
    try:
        jax_process._BY_FUNCTION.clear()
        for i in range(3 * jax_process._FUNCTIONS_KEPT):
            jax_process._row(f"f{i}")["trace_s"] += 1.0 + i
        assert len(jax_process._BY_FUNCTION) <= jax_process._FUNCTIONS_KEPT
        shown = jax_process.compile_stats()["functions"]
        assert len(shown) == jax_process._FUNCTIONS_SHOWN + 1 and "other" in shown
        assert f"f{3 * jax_process._FUNCTIONS_KEPT - 1}" in shown
        n = 3 * jax_process._FUNCTIONS_KEPT
        assert sum(r["trace_s"] for r in shown.values()) == pytest.approx(n + n * (n - 1) / 2)
    finally:
        jax_process._BY_FUNCTION.clear()
        jax_process._BY_FUNCTION.update(kept)


# ------------------------------------------------------------ the step clock
def _compile_for(seconds, traces=1):
    """What the counter's listeners do while jax compiles for `seconds`."""
    time.sleep(seconds)
    COMPILE_TOTALS["seconds"] += seconds
    COMPILE_TOTALS["trace_s"] += seconds
    COMPILE_TOTALS["traces"] += traces
    COMPILE_TOTALS["events"] += traces


@pytest.mark.parametrize("marked", [False, True])
def test_step_clock_moves_compile_seconds_into_compile(marked):
    saved = dict(COMPILE_TOTALS)
    try:
        clock = StepClock("g", 0)
        clock.mark("data_wait")
        t0 = time.perf_counter()
        time.sleep(0.01)
        clock.mark("compile" if marked else "step_exec")
        t1 = time.perf_counter()
        _compile_for(0.03, traces=3)
        clock.mark("step_exec")
        t2 = time.perf_counter()
        time.sleep(0.01)
        telem = clock.close_step()
        t3 = time.perf_counter()
        phases = telem["phases"]
        # Conservation: the phases still partition the step's wall time.
        assert sum(phases.values()) == pytest.approx(telem["step_wall_s"], abs=1e-6)
        # The counter's 0.03 s, once, marked or not (marked: all of the phase,
        # the sleep's overshoot too).
        assert phases["compile"] == pytest.approx(t2 - t1 if marked else 0.03, abs=0.002)
        assert phases["data_wait"] == pytest.approx(t1 - t0, abs=0.002)
        # The rest, with the clock's own start (its first imports) before t0.
        assert phases["step_exec"] >= t3 - t2 + (0.0 if marked else t2 - t1 - 0.03) - 0.002
        assert telem["compile"] == {"traces": 3, "trace_s": pytest.approx(0.03)}
        # A step in which nothing compiled carries no counts and no compile phase.
        time.sleep(0.005)
        quiet = clock.close_step()
        assert "compile" not in quiet and "compile" not in quiet["phases"]
        totals = clock.finalize()
        assert totals["phases"]["compile"] == pytest.approx(phases["compile"])
        assert totals["compile"]["functions"] is not None
    finally:
        COMPILE_TOTALS.update(saved)


def test_span_log_nests_and_travels():
    log = SpanLog("g", push=False)
    with log.span("outer", a=1) as outer:
        wire = log.wire()
        with log.span("inner"):
            pass
    worker = SpanLog.from_wire(wire, rank=3)
    with worker.span("remote"):
        pass
    inner, outer_done = log.take()
    (remote,) = worker.take()
    assert outer_done is not outer and outer_done["span_id"] == outer["span_id"]
    assert inner["parent_id"] == remote["parent_id"] == outer["span_id"]
    assert inner["trace_id"] == remote["trace_id"] == outer["trace_id"]
    assert remote["attributes"] == {"gang": "g", "rank": 3}
    assert outer_done["attributes"] == {"gang": "g", "a": 1} and outer_done["kind"] == "bringup"
    assert not [k for k in remote if k.startswith("_")] and log.take() == []
