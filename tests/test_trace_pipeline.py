"""End-to-end request tracing: cross-process propagation, critical-path
attribution, sampling, tail-keep, exemplars, and knob-off parity.

The acceptance shape: one Serve HTTP request yields ONE connected trace
spanning proxy -> router -> replica -> nested task, and
state.latency_report() attributes >=95% of its wall time to named
components (ISSUE 14)."""

import json
import os
import threading
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu._private import critical_path
from ray_tpu.util import tracing


@pytest.fixture(autouse=True)
def _tracing_reset():
    yield
    # enable()/configure_sampling are process-global: restore defaults so
    # other modules never record spans or inherit a test's sample rate.
    tracing._enabled = False
    tracing._exporter = None
    tracing._rate_override = None
    tracing._sampler = None
    tracing._state.span = None  # no current-span leak across tests
    with tracing._lock:
        tracing._buffer[:] = []
    os.environ.pop("RAY_TPU_TRACING", None)
    tracing.refresh_env()


def _wait_for(fn, timeout=15.0, interval=0.2):
    deadline = time.time() + timeout
    last = None
    while time.time() < deadline:
        last = fn()
        if last:
            return last
        time.sleep(interval)
    return last


# --------------------------------------------------------------- acceptance
def test_serve_request_one_connected_trace_and_latency_report():
    """Proxy mints the root; router, replica execute, and the replica's
    nested task all join the SAME trace with correct parent links, and the
    critical path attributes >=95% of the wall time to named components."""
    from ray_tpu import serve
    from ray_tpu.util import state

    ray_tpu.init(num_cpus=4, _system_config={"trace_sample_rate": 1.0})
    tracing.enable()
    try:
        @ray_tpu.remote
        def nested(x):
            return x * 2

        @serve.deployment
        class App:
            def __call__(self, req):
                return {"out": ray_tpu.get(nested.remote(3))}

        serve.run(App.bind(), route_prefix="/app", port=0)
        from ray_tpu._private.worker import global_worker

        port = global_worker.context.serve_directory()[0]["port"]
        resp = urllib.request.urlopen(f"http://127.0.0.1:{port}/app",
                                      timeout=30)
        assert resp.status == 200

        def full_trace():
            traces = [t for t in state.list_traces()
                      if t["root_kind"] == "request"]
            if not traces:
                return None
            t = state.get_trace(traces[-1]["trace_id"])
            kinds = {s["kind"] for s in t["spans"]}
            names = {s["name"] for s in t["spans"]}
            if {"request", "router", "submit", "execute"} <= kinds and any(
                "nested" in n for n in names
            ):
                return t
            return None

        t = _wait_for(full_trace, timeout=20)
        assert t is not None, state.list_traces()
        spans = {s["span_id"]: s for s in t["spans"]}
        # ONE trace id across every span.
        assert len({s["trace_id"] for s in t["spans"]}) == 1
        by_name = {}
        for s in t["spans"]:
            by_name.setdefault(s["name"].split("::")[0], s)
        root = [s for s in t["spans"] if not s.get("parent_id")]
        assert len(root) == 1 and root[0]["kind"] == "request"
        # Parent chain: request <- router <- actor submit <- execute <-
        # nested submit <- nested execute (each parent resolves in-trace).
        for s in t["spans"]:
            if s.get("parent_id"):
                assert s["parent_id"] in spans, s
        exec_replica = next(s for s in t["spans"]
                            if s["kind"] == "execute"
                            and "handle_request" in s["name"])
        nested_submit = next(s for s in t["spans"]
                             if s["kind"] == "submit" and "nested" in s["name"])
        assert nested_submit["parent_id"] == exec_replica["span_id"]
        router = next(s for s in t["spans"] if s["kind"] == "router")
        assert router["parent_id"] == root[0]["span_id"]
        # Attribution: >=95% of the request's wall time lands on NAMED
        # components (the acceptance bar).
        attr = t["attribution"]
        assert attr["coverage"] >= 0.95, attr
        assert "exec" in attr["components"], attr
        # The latency report aggregates the same attribution.
        rep = state.latency_report()
        assert rep["traces"] >= 1
        assert rep["coverage"] >= 0.95, rep
        assert set(rep["components"]) <= set(critical_path.COMPONENTS)
        assert "head_loop" in rep["components"] or "exec" in rep["components"]
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass
        ray_tpu.shutdown()


# -------------------------------------------------------------- propagation
def test_transfer_span_attaches_to_owning_context(tmp_path):
    """A PullManager.pull that runs under a trace context emits a
    "transfer" span parented on that context (a slow get shows WHICH
    transfer stalled)."""
    from ray_tpu._private import object_transfer
    from ray_tpu._private.config import Config
    from ray_tpu._private.ids import JobID, ObjectID, TaskID
    from ray_tpu._private.object_store import ObjectMeta

    tracing.enable()

    class _StubPulls(object_transfer.PullManager):
        def __init__(self):
            super().__init__(str(tmp_path), Config(), authkey=b"x")

        def _start_transfer(self, req):
            pass

    pm = _StubPulls()
    oid = ObjectID.for_put(TaskID.for_driver(JobID.from_int(1)), 1)
    meta = ObjectMeta(object_id=oid, size=64,
                      segment=f"/fake/{oid.hex()}", node_id=b"n" * 16)

    def finish_soon():
        time.sleep(0.1)
        with pm._lock:
            req = pm._reqs[oid.binary()]
        with open(req.final_path, "wb") as f:
            f.write(b"y" * 64)
        req.fh = None
        req.tmp_path = None
        with pm._lock:
            pm._settle_locked(req, "done", None)

    threading.Thread(target=finish_soon, daemon=True).start()
    with tracing.span("owning_get") as outer:
        path = pm.pull(meta, [(b"n" * 16, "127.0.0.1:1")])
    assert path == os.path.join(str(tmp_path), oid.hex())
    with tracing._lock:
        spans = list(tracing._buffer)
    transfer = [s for s in spans if s["kind"] == "transfer"]
    assert transfer, spans
    assert transfer[0]["trace_id"] == outer["trace_id"]
    assert transfer[0]["parent_id"] == outer["span_id"]
    assert transfer[0]["attributes"]["object_id"] == oid.hex()
    assert transfer[0]["end"] - transfer[0]["start"] >= 0.05


def test_failed_pull_records_error_span(tmp_path):
    from ray_tpu._private import object_transfer
    from ray_tpu._private.config import Config
    from ray_tpu._private.ids import JobID, ObjectID, TaskID
    from ray_tpu._private.object_store import ObjectMeta

    tracing.enable()

    class _StubPulls(object_transfer.PullManager):
        def __init__(self):
            super().__init__(str(tmp_path), Config(), authkey=b"x")

        def _start_transfer(self, req):
            self._finish_error(req, object_transfer.PullFailed("stub"))

    pm = _StubPulls()
    oid = ObjectID.for_put(TaskID.for_driver(JobID.from_int(1)), 2)
    meta = ObjectMeta(object_id=oid, size=8,
                      segment=f"/fake/{oid.hex()}", node_id=b"n" * 16)
    with tracing.span("owning_get"):
        with pytest.raises(object_transfer.PullFailed):
            pm.pull(meta, [(b"n" * 16, "127.0.0.1:1")])
    with tracing._lock:
        transfer = [s for s in tracing._buffer if s["kind"] == "transfer"]
    assert transfer and transfer[0]["status"] == "ERROR"


# ----------------------------------------------------------------- sampling
def test_seeded_sampling_determinism():
    """Same seed -> identical keep/drop sequence; different seed differs."""
    tracing._enabled = True  # no runtime needed for the draw itself
    tracing.configure_sampling(rate=0.5, seed=1234)
    first = [tracing._should_sample() for _ in range(200)]
    tracing.configure_sampling(rate=0.5, seed=1234)
    second = [tracing._should_sample() for _ in range(200)]
    assert first == second
    assert any(first) and not all(first)  # rate actually applied
    tracing.configure_sampling(rate=0.5, seed=99)
    third = [tracing._should_sample() for _ in range(200)]
    assert third != first


def test_one_sampling_draw_per_root_across_paths():
    """The `.remote()` fast-path gate and the general path's span share ONE
    sampling decision: root_unsampled() followed by a presampled start_span
    consumes exactly one draw, so the keep sequence matches a plain
    _should_sample() sequence (no rate-squaring for no-arg tasks, seeded
    replay stays aligned)."""
    tracing._enabled = True
    tracing.configure_sampling(rate=0.5, seed=7)
    expected = [tracing._should_sample() for _ in range(40)]
    tracing.configure_sampling(rate=0.5, seed=7)  # reset the sequence
    decisions = []
    for _ in range(40):
        unsampled = tracing.root_unsampled()
        if not unsampled:
            s = tracing.start_span("r", "submit", presampled=True)
            assert s is not None  # the pre-made decision is trusted, no redraw
            tracing.end_span(s)
        decisions.append(not unsampled)
    assert decisions == expected
    # presampled bypasses the draw entirely even at rate 0.
    tracing.configure_sampling(rate=0.0)
    s = tracing.start_span("r", "submit", presampled=True)
    assert s is not None
    tracing.end_span(s)


def test_router_span_flushed_on_route_failure():
    """A shed / controller failure inside route() still closes the router
    span (status ERROR) — the failed requests are exactly the ones a trace
    must explain."""
    from ray_tpu.serve.handle import Router

    class _DeadMethod:
        def remote(self, *a, **k):
            raise RuntimeError("controller gone")

    class _DeadController:
        def __getattr__(self, name):
            return _DeadMethod()

    tracing.enable()
    router = Router("traced_dep", _DeadController())
    ctx = {"trace_id": "t" * 32, "parent_id": "p" * 16}
    with pytest.raises(RuntimeError):
        router.route("__call__", (), {}, force_refresh=True, trace_ctx=ctx)
    with tracing._lock:
        rspans = [s for s in tracing._buffer
                  if s["kind"] == "router" and "traced_dep" in s["name"]]
    assert rspans and rspans[0]["status"] == "ERROR"
    assert rspans[0]["trace_id"] == "t" * 32
    router.close()


def test_unsampled_root_propagates_nothing_but_children_record():
    tracing.enable(sample_rate=0.0)
    # Root loses the draw -> no span at all.
    assert tracing.start_span("root", "submit") is None
    # A span with an explicit (sampled) parent context always records.
    ctx = {"trace_id": "t" * 32, "parent_id": "p" * 16}
    child = tracing.start_span("child", "execute", trace_context=ctx)
    assert child is not None and child["trace_id"] == "t" * 32
    tracing.end_span(child)
    # context_of(None) is None: callers propagate nothing for dropped roots.
    assert tracing.context_of(None) is None


def test_tail_keep_preserves_slow_unsampled_spans():
    from ray_tpu._private.config import get_config

    cfg = get_config()
    old = cfg.trace_keep_latency_s
    cfg.trace_keep_latency_s = 0.05
    try:
        tracing.enable(sample_rate=0.0)
        # Fast unsampled tail-keep span: dropped at end.
        s = tracing.start_span("fast", "request", detached=True,
                               tail_keep=True)
        assert s is not None and s.get("_provisional")
        assert tracing.context_of(s) is None  # children must not record
        tracing.end_span(s)
        with tracing._lock:
            assert all(x["name"] != "fast" for x in tracing._buffer)
        # Slow one: kept, marked keep="tail".
        s = tracing.start_span("slow", "request", detached=True,
                               tail_keep=True)
        time.sleep(0.08)
        tracing.end_span(s)
        with tracing._lock:
            kept = [x for x in tracing._buffer if x["name"] == "slow"]
        assert kept and kept[0]["keep"] == "tail"
        # record_span honors the same contract.
        t0 = time.time()
        tracing.record_span("slow_pull", "transfer", t0 - 0.1, t0,
                            trace_context=None, tail_keep=True)
        tracing.record_span("fast_pull", "transfer", t0 - 0.001, t0,
                            trace_context=None, tail_keep=True)
        with tracing._lock:
            names = [x["name"] for x in tracing._buffer]
        assert "slow_pull" in names and "fast_pull" not in names
    finally:
        cfg.trace_keep_latency_s = old


def test_buffer_bounded_when_enabled_before_init():
    """enable() before any runtime exists must not grow memory forever:
    the buffer caps and overflow is counted."""
    old_cap = tracing._buffer_cap
    drops0 = tracing._DROPPED["spans"]
    try:
        tracing.enable()
        tracing._buffer_cap = 50  # after enable(): enable re-reads config
        for i in range(120):
            s = tracing.start_span(f"s{i}", "custom")
            tracing.end_span(s)
        with tracing._lock:
            assert len(tracing._buffer) <= 50
        assert tracing._DROPPED["spans"] - drops0 >= 70
        # flush with no runtime context: a no-op, not an error.
        tracing.flush_spans()
    finally:
        tracing._buffer_cap = old_cap


# ------------------------------------------------------------ knob-off parity
def test_knob_off_parity_zero_spans_zero_traffic():
    """Tracing never enabled: no span is recorded anywhere, the head's
    span ring never sees a push, and the trace surfaces come back empty."""
    from ray_tpu._private.worker import global_worker
    from ray_tpu.util import state

    assert not tracing.is_enabled()
    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote
        def f(x):
            return x + 1

        assert ray_tpu.get([f.remote(i) for i in range(50)],
                           timeout=60) == list(range(1, 51))
        time.sleep(1.2)  # a flush period: nothing must have flushed
        sched = global_worker.node
        assert len(sched.gcs.trace_spans) == 0
        assert sched.gcs.trace_spans_total == 0  # zero pushes ever arrived
        with tracing._lock:
            assert tracing._buffer == []
        assert tracing.collect_spans() == []
        assert state.list_traces() == []
        rep = state.latency_report()
        assert rep["traces"] == 0
    finally:
        ray_tpu.shutdown()


# --------------------------------------------------------- critical path unit
def test_critical_path_attribution_synthetic():
    """Deepest-interval sweep: stage intervals win over their span, parents
    keep only unexplained time, totals sum to the trace wall time."""
    t0 = 1000.0
    spans = [
        {"trace_id": "T", "span_id": "req", "parent_id": None,
         "kind": "request", "name": "request::app", "start": t0,
         "end": t0 + 1.0, "status": "OK", "attributes": {}, "pid": 1},
        {"trace_id": "T", "span_id": "rt", "parent_id": "req",
         "kind": "router", "name": "route::app", "start": t0 + 0.1,
         "end": t0 + 0.2, "status": "OK", "attributes": {}, "pid": 1},
        {"trace_id": "T", "span_id": "sub", "parent_id": "rt",
         "kind": "submit", "name": "actor::m", "start": t0 + 0.12,
         "end": t0 + 0.15, "status": "OK",
         "attributes": {"task_id": "task1"}, "pid": 1},
        {"trace_id": "T", "span_id": "ex", "parent_id": "sub",
         "kind": "execute", "name": "execute::m", "start": t0 + 0.3,
         "end": t0 + 0.8, "status": "OK",
         "attributes": {"task_id": "task1"}, "pid": 2},
    ]
    stages = {"task1": {
        "submit": t0 + 0.12, "queued": t0 + 0.14, "lease_granted": t0 + 0.25,
        "args_fetched": t0 + 0.3, "exec_start": t0 + 0.3,
        "exec_end": t0 + 0.75, "result_stored": t0 + 0.8,
    }}
    attr = critical_path.attribute(spans, stages)
    comp = attr["components"]
    assert attr["coverage"] == pytest.approx(1.0)
    assert sum(comp.values()) == pytest.approx(attr["total_s"])
    # queued -> lease_granted is the head-loop number.
    assert comp["head_loop"] == pytest.approx(0.11, abs=1e-6)
    assert comp["exec"] == pytest.approx(0.45, abs=1e-6)
    assert comp["store_results"] == pytest.approx(0.05, abs=1e-6)
    # result_stored -> request end is completion delivery.
    assert comp["done_delivery"] == pytest.approx(0.2, abs=1e-6)
    assert "proxy_queue" in comp
    # Summary + report over the same trace.
    rep = critical_path.latency_report(spans, stages)
    assert rep["traces"] == 1
    assert rep["components"]["exec"]["share"] > 0.3


def test_trace_summary_and_grouping():
    spans = [
        {"trace_id": "A", "span_id": "1", "parent_id": None, "kind": "submit",
         "name": "task::f", "start": 1.0, "end": 1.5, "status": "OK",
         "attributes": {}, "pid": 1},
        {"trace_id": "A", "span_id": "2", "parent_id": "1", "kind": "execute",
         "name": "execute::f", "start": 1.1, "end": 1.4, "status": "ERROR",
         "attributes": {}, "pid": 2, "keep": "tail"},
        {"trace_id": "B", "span_id": "3", "parent_id": None, "kind": "custom",
         "name": "x", "start": 2.0, "end": 2.1, "status": "OK",
         "attributes": {}, "pid": 1},
    ]
    groups = critical_path.group_traces(spans)
    assert set(groups) == {"A", "B"}
    sa = critical_path.trace_summary("A", groups["A"])
    assert sa["spans"] == 2 and sa["status"] == "ERROR" and sa["tail_kept"]
    assert sa["duration_s"] == pytest.approx(0.5)
    assert sa["root"] == "task::f"


# ----------------------------------------------------------------- exemplars
def test_exemplar_pipeline_store_and_alert_link():
    """Histogram/gauge exemplars ride the snapshot into the series store,
    come back from query(), and a firing alert links the trace ids."""
    from ray_tpu._private.timeseries import AlertEngine, TimeSeriesStore
    from ray_tpu.util.metrics import Gauge, Histogram

    h = Histogram("ray_tpu_test_exemplar_hist_s", "t", boundaries=(0.1, 1.0))
    g = Gauge("ray_tpu_test_exemplar_gauge", "t")
    h.observe(0.05, {"app": "a"})                      # untraced: no exemplar
    h.observe(0.7, {"app": "a"}, exemplar="trace-slow")
    g.set(0.7, {"app": "a"}, exemplar="trace-slow")
    hs, gs = h._snapshot(), g._snapshot()
    assert hs["exemplars"] and gs["exemplars"]
    assert hs["exemplars"][0][1][0][2] == "trace-slow"

    store = TimeSeriesStore(step_s=0.05, retention_s=60)
    store.ingest("77", [hs, gs])
    res = store.query("ray_tpu_test_exemplar_gauge")
    ex = res["series"][0].get("exemplars")
    assert ex and ex[0]["trace_id"] == "trace-slow"
    assert store.exemplars_for("ray_tpu_test_exemplar_hist_s")[0][
        "trace_id"] == "trace-slow"

    events = []
    engine = AlertEngine(
        store,
        [{"name": "test_rule", "metric": "ray_tpu_test_exemplar_gauge",
          "kind": "gauge", "agg": "max", "window_s": 60.0,
          "op": ">", "threshold": 0.5, "for_s": 0.0}],
        event_sink=lambda kind, msg, **data: events.append((kind, data)),
    )
    engine.evaluate()
    firing = [e for e in events if e[0] == "alert_firing"]
    assert firing and firing[0][1]["exemplar_trace_ids"] == ["trace-slow"]
    payload = engine.payload()[0]
    assert payload["exemplars"][0]["trace_id"] == "trace-slow"


# ---------------------------------------------------------------- surfaces
def test_dashboard_traces_and_latency_endpoints():
    from ray_tpu.dashboard import start_dashboard

    ray_tpu.init(num_cpus=2, _system_config={"trace_sample_rate": 1.0})
    tracing.enable()
    try:
        @ray_tpu.remote
        def f():
            return 1

        assert ray_tpu.get(f.remote(), timeout=30) == 1
        tracing.flush_spans()
        server = start_dashboard(port=0)
        base = f"http://127.0.0.1:{server.port}"
        traces = _wait_for(lambda: json.loads(urllib.request.urlopen(
            f"{base}/api/traces", timeout=15).read()))
        assert traces and {"trace_id", "duration_s", "spans"} <= set(traces[-1])
        one = json.loads(urllib.request.urlopen(
            f"{base}/api/traces?trace_id={traces[-1]['trace_id']}",
            timeout=15).read())
        assert one["attribution"]["total_s"] >= 0
        rep = json.loads(urllib.request.urlopen(
            f"{base}/api/latency", timeout=15).read())
        assert rep["traces"] >= 1
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(f"{base}/api/traces?trace_id=deadbeef",
                                   timeout=15)
        assert err.value.code == 400
        server.stop()
    finally:
        ray_tpu.shutdown()


def test_flush_is_append_proportional():
    """The spans_push path appends O(new) per flush: pushing twice grows the
    head ring by exactly the new batches (no read-modify-rewrite of
    history), and the ring honors its cap."""
    from ray_tpu._private.worker import global_worker

    ray_tpu.init(num_cpus=1, _system_config={"trace_sample_rate": 1.0})
    tracing.enable()
    try:
        sched = global_worker.node
        for i in range(5):
            s = tracing.start_span(f"a{i}", "custom")
            tracing.end_span(s)
        tracing.flush_spans()
        # push is a fire-and-forget loop command: wait for the drain.
        _wait_for(lambda: len(sched.gcs.trace_spans) >= 5, timeout=5)
        n1 = len(sched.gcs.trace_spans)
        assert n1 >= 5
        for i in range(3):
            s = tracing.start_span(f"b{i}", "custom")
            tracing.end_span(s)
        tracing.flush_spans()
        _wait_for(lambda: len(sched.gcs.trace_spans) >= n1 + 3, timeout=5)
        assert len(sched.gcs.trace_spans) == n1 + 3
        # Ring cap enforcement.
        sched.gcs.set_trace_span_cap(4)
        assert len(sched.gcs.trace_spans) == 4
        sched.gcs.append_trace_spans(
            [{"trace_id": "x", "span_id": str(i), "start": time.time()}
             for i in range(10)]
        )
        assert len(sched.gcs.trace_spans) == 4
    finally:
        ray_tpu.shutdown()
