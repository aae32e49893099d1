"""Serve ingress tier: admission control + shedding, graceful drain,
listener lifecycle, SLO autoscaling, proxy failover, /api/serve.

Reference surfaces: `python/ray/serve/tests/test_proxy_state.py` (proxy
fleet), `test_backpressure.py` (max_queued_requests -> 503),
`test_graceful_shutdown.py` (drain), `test_autoscaling_policy.py` (SLO
scaling). Multi-node tests build their own virtual cluster (the shared
single-node session cannot host two proxies)."""

import gc
import threading
import time
import urllib.error
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture
def serve_session():
    ray_tpu.init(num_cpus=8)
    yield
    try:
        serve.shutdown()
    except Exception:
        pass
    ray_tpu.shutdown()


def _get(url, timeout=30):
    """(status, body, headers) — 503s come back as data, not exceptions."""
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read(), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


# ---------------------------------------------------------------------------
# @serve.batch shedding (unit level: the queue itself)
# ---------------------------------------------------------------------------
def test_batch_queue_cap_sheds_immediately():
    """A submit finding the queue at max_queue_len is rejected in O(1) with
    RequestShedded — not parked behind a full batch to time out later."""
    import asyncio

    from ray_tpu.serve._private.common import RequestShedded
    from ray_tpu.serve.batching import _BatchQueue

    async def runner():
        started = asyncio.Event()
        release = asyncio.Event()

        async def fn(items):
            started.set()
            await release.wait()
            return [i * 2 for i in items]

        q = _BatchQueue(fn, max_batch_size=2, batch_wait_timeout_s=0.01,
                        max_queue_len=3)
        # Fill: two go into the executing batch, then refill the queue.
        t1 = asyncio.ensure_future(q.submit(None, 1))
        t2 = asyncio.ensure_future(q.submit(None, 2))
        await started.wait()
        t3 = asyncio.ensure_future(q.submit(None, 3))
        t4 = asyncio.ensure_future(q.submit(None, 4))
        t5 = asyncio.ensure_future(q.submit(None, 5))
        await asyncio.sleep(0.05)  # let them enqueue while fn blocks
        t0 = time.monotonic()
        with pytest.raises(RequestShedded) as ei:
            await q.submit(None, 6)
        assert time.monotonic() - t0 < 0.1  # FAST shed, no batch wait
        assert ei.value.reason == "batch_queue"
        assert q.shed_count == 1
        release.set()
        assert await t1 == 2 and await t2 == 4
        assert await t3 == 6 and await t4 == 8 and await t5 == 10

    asyncio.run(runner())


def test_batch_shed_timeout_vs_flush_race():
    """Members that waited past shed_timeout_s shed INDIVIDUALLY at flush
    time (503, not a whole-batch timeout), and the flush-timer vs shed race
    settles every future exactly once: each member is executed XOR shed."""
    import asyncio

    from ray_tpu.serve._private.common import RequestShedded
    from ray_tpu.serve.batching import _BatchQueue

    async def runner():
        release = asyncio.Event()
        calls = []

        async def fn(items):
            calls.append(list(items))
            await release.wait()
            return list(items)

        q = _BatchQueue(fn, max_batch_size=4, batch_wait_timeout_s=0.01,
                        shed_timeout_s=0.15)
        # First member starts a batch that blocks in fn (holding the
        # drainer); the rest queue behind it and go stale.
        t1 = asyncio.ensure_future(q.submit(None, "a"))
        await asyncio.sleep(0.03)
        stale = [asyncio.ensure_future(q.submit(None, f"s{i}"))
                 for i in range(3)]
        await asyncio.sleep(0.25)  # > shed_timeout_s while fn still blocks
        fresh = asyncio.ensure_future(q.submit(None, "fresh"))
        await asyncio.sleep(0.01)
        release.set()
        assert await t1 == "a"  # already executing: never shed
        shed = 0
        for t in stale:
            try:
                await t
            except RequestShedded:
                shed += 1
        assert shed == 3, "stale queued members must shed individually"
        # The fresh member (well under the deadline) executes normally.
        assert await fresh == "fresh"
        assert q.shed_count == 3
        # Exactly-once settlement: nothing shed was also executed.
        executed = [x for batch in calls for x in batch]
        assert executed.count("a") == 1 and executed.count("fresh") == 1
        assert not any(x.startswith("s") for x in executed)

    asyncio.run(runner())


def test_batch_shed_reason_survives_the_wire(serve_session):
    """A replica-raised batch shed must reach the HTTP client with its real
    reason and Retry-After. Regression: default exception pickling (and the
    RayTaskError.as_instanceof_cause MRO) reset RequestShedded's attributes
    to 'overload'/1.0 on the way to the proxy."""
    import json

    @serve.deployment(max_concurrent_queries=4)
    class Batched:
        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.02,
                     max_queue_len=2)
        async def run(self, items):
            import asyncio

            await asyncio.sleep(0.3)
            return list(items)

        async def __call__(self, request):
            return await self.run(1)

    serve.run(Batched.bind(), route_prefix="/batched", port=0)
    port = serve.http_port()
    url = f"http://127.0.0.1:{port}/batched"
    results = []
    lock = threading.Lock()

    def fire():
        out = _get(url)
        with lock:
            results.append(out)

    threads = [threading.Thread(target=fire) for _ in range(10)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    sheds = [(b, h) for s, b, h in results if s == 503]
    assert sheds, [s for s, _b, _h in results]
    for body, headers in sheds:
        assert json.loads(body)["reason"] == "batch_queue", body
        ra = headers["Retry-After"]
        assert ra.isdigit() and int(ra) >= 1, ra  # RFC 9110 delay-seconds


# ---------------------------------------------------------------------------
# Handle-side long-poll listener lifecycle (leak regression)
# ---------------------------------------------------------------------------
def test_listener_slots_stable_across_50_redeploys(serve_session):
    """A deleted/GC'd ServeHandle must unregister its listen_for_change
    parker: repeated deploy/use/delete cycles must not accumulate one parked
    listener each at the controller (the pre-fix behavior: the listener
    thread held the router alive forever and re-parked until process exit).
    12 cycles keeps the signal unambiguous (pre-fix count would be ~12 vs
    the <=3 bound) at a quarter of the tier-1 wall-clock of the original
    50-cycle version."""

    @serve.deployment
    def echo(x):
        return x

    controller = None
    for i in range(12):
        handle = serve.run(echo.bind(), _blocking_http=False)
        controller = handle._controller
        assert handle.remote(i).result() == i  # forces router + listener
        serve.delete("echo")
        del handle
        gc.collect()
    gc.collect()
    # cancel_listener unparks dropped listeners; give the threads a beat.
    deadline = time.time() + 15
    count = None
    while time.time() < deadline:
        count = ray_tpu.get(controller.listener_count.remote())
        if count <= 3:
            break
        time.sleep(0.5)
    assert count is not None and count <= 3, (
        f"{count} listeners still parked after 12 redeploys (leak)"
    )


# ---------------------------------------------------------------------------
# Proxy admission control: per-app cap -> fast 503 + Retry-After
# ---------------------------------------------------------------------------
def test_proxy_sheds_over_app_cap_and_recovers(serve_session):
    @serve.deployment(max_concurrent_queries=1, max_queued_requests=2)
    class Slow:
        def __call__(self, request):
            time.sleep(0.4)
            return "done"

    serve.run(Slow.bind(), route_prefix="/slow", port=0)
    port = serve.http_port()
    url = f"http://127.0.0.1:{port}/slow"

    results = []
    lock = threading.Lock()

    def fire():
        t0 = time.monotonic()
        status, body, headers = _get(url, timeout=30)
        with lock:
            results.append((status, time.monotonic() - t0, headers))

    threads = [threading.Thread(target=fire) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    codes = [r[0] for r in results]
    assert codes.count(200) >= 2, codes  # admitted window completes
    sheds = [r for r in results if r[0] == 503]
    assert sheds, f"no 503s at 4x the cap: {codes}"
    for status, elapsed, headers in sheds:
        assert elapsed < 1.0, "shed must be fast, not queued"
        assert "Retry-After" in headers
    # Recovery: the shed state is not sticky.
    status, body, _ = _get(url)
    assert status == 200 and b"done" in body
    # Shed counters surfaced on the proxy's stats endpoint.
    proxy = serve.api._get_proxy(create=False)
    stats = ray_tpu.get(proxy.ingress_stats.remote())
    assert stats["apps"]["Slow"]["shed"] >= 1
    assert stats["apps"]["Slow"]["cap"] == 2


def test_router_inflight_cap_sheds():
    """Router half of admission control: with the cap factor armed, a flood
    past every replica's max_concurrent_queries x factor sheds instead of
    queueing without bound."""
    ray_tpu.init(
        num_cpus=8,
        _system_config={"serve_replica_inflight_cap_factor": 2.0},
    )
    try:
        @serve.deployment(max_concurrent_queries=1)
        class Sleepy:
            def __call__(self, x):
                time.sleep(0.5)
                return x

        handle = serve.run(Sleepy.bind(), _blocking_http=False)
        from ray_tpu.serve._private.common import RequestShedded

        responses = []
        shed = 0
        # One replica, mcq=1, factor 2 -> shed once >= 2 are in flight.
        try:
            for i in range(8):
                responses.append(handle.remote(i))
        except RequestShedded as e:
            shed += 1
            assert e.reason == "replica_inflight"
        assert shed or len(responses) < 8, (
            "flood past the inflight cap never shed"
        )
        for r in responses:
            assert r.result(timeout=30) is not None
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass
        ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# Graceful drain: replica stop under live load drops nothing admitted
# ---------------------------------------------------------------------------
def test_replica_drain_zero_dropped_requests(serve_session):
    @serve.deployment(num_replicas=2, max_concurrent_queries=4)
    class Work:
        def __call__(self, x):
            time.sleep(0.25)
            return x * 2

    handle = serve.run(Work.bind(), _blocking_http=False)
    results = {}
    errors = []
    lock = threading.Lock()

    def call(i):
        try:
            v = handle.remote(i).result(timeout=60)
            with lock:
                results[i] = v
        except Exception as e:  # noqa: BLE001 — the assertion wants it all
            with lock:
                errors.append((i, repr(e)))

    threads = [threading.Thread(target=call, args=(i,)) for i in range(16)]
    for t in threads:
        t.start()
    time.sleep(0.15)  # requests admitted and spread over both replicas
    # Scale down 2 -> 1 mid-load: the dropped replica must finish its
    # inflight window (queued actor calls included) before the kill.
    serve.run(Work.options(num_replicas=1).bind(), _blocking_http=False)
    for t in threads:
        t.join()
    assert not errors, f"admitted requests dropped during drain: {errors}"
    assert results == {i: i * 2 for i in range(16)}
    st = serve.status()
    deadline = time.time() + 20
    while time.time() < deadline and st["Work"]["num_replicas"] != 1:
        time.sleep(0.2)
        st = serve.status()
    assert st["Work"]["num_replicas"] == 1


# ---------------------------------------------------------------------------
# SLO-aware autoscaling: p95 violation scales up despite calm queue depth
# ---------------------------------------------------------------------------
def test_slo_autoscaling_scales_on_p95(serve_session):
    @serve.deployment(
        autoscaling_config={
            "min_replicas": 1,
            "max_replicas": 3,
            "upscale_delay_s": 0.0,
            "downscale_delay_s": 300.0,
            "target_route_wait_p95_s": 0.05,
        }
    )
    def f(x):
        return x

    handle = serve.run(f.bind(), _blocking_http=False)
    assert handle.remote(1).result() == 1
    controller = handle._controller
    assert serve.status()["f"]["num_replicas"] == 1
    # Feed the controller a violating p95 with ZERO queue depth: only the
    # SLO path can grow the deployment.
    deadline = time.time() + 20
    grew = False
    while time.time() < deadline:
        ray_tpu.get(
            controller.report_load.remote("f", "fake-router", 0, 0.5)
        )
        if serve.status()["f"]["num_replicas"] >= 2:
            grew = True
            break
        time.sleep(0.2)
    assert grew, "sustained p95 violation never scaled up"


# ---------------------------------------------------------------------------
# Dashboard /api/serve
# ---------------------------------------------------------------------------
def test_dashboard_api_serve(serve_session):
    from ray_tpu.dashboard.head import start_dashboard

    @serve.deployment
    def ping(request):
        return "pong"

    serve.run(ping.bind(), route_prefix="/ping", port=0)
    port = serve.http_port()
    status, body, _ = _get(f"http://127.0.0.1:{port}/ping")
    assert status == 200
    dash = start_dashboard(port=0)
    try:
        import json

        status, body, _ = _get(f"http://127.0.0.1:{dash.port}/api/serve")
        assert status == 200
        payload = json.loads(body)
        assert "ping" in payload["apps"]
        app = payload["apps"]["ping"]
        assert app["route_prefix"] == "/ping"
        assert app["replicas"], "replica list missing"
        assert "max_queued_requests" in app
        # Filtered view.
        status, body, _ = _get(
            f"http://127.0.0.1:{dash.port}/api/serve?app=ping"
        )
        assert status == 200 and "ping" in json.loads(body)["apps"]
        # PR 5 error-shape convention: bad query param -> JSON 400.
        status, body, _ = _get(
            f"http://127.0.0.1:{dash.port}/api/serve?app=nope"
        )
        assert status == 400
        assert "unknown app" in json.loads(body)["error"]
    finally:
        dash.stop()


# ---------------------------------------------------------------------------
# Multi-proxy: failover under load + wire-protocol drain
# ---------------------------------------------------------------------------
def test_proxy_failover_under_load():
    """SIGKILL one of two proxies mid-load: zero 5xx beyond the in-flight
    window at the SURVIVOR, routing-table convergence there, and the
    controller's reconcile loop brings the fleet back to two."""
    import os
    import signal

    from ray_tpu.actor import ActorHandle
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(head_node_args={"num_cpus": 2})
    try:
        cluster.add_node(num_cpus=2)

        @serve.deployment(num_replicas=2, max_concurrent_queries=8)
        def hello(request):
            return "ok"

        serve.run(hello.bind(), route_prefix="/hello", _blocking_http=False)
        serve.start(proxy_location="EveryNode")
        ports = {
            nid: p for nid, p in serve.proxy_ports().items() if nid != "head"
        }
        assert len(ports) == 2, ports

        controller = serve.api._get_controller()
        proxies = ray_tpu.get(controller.get_proxies.remote())
        victim_nid = sorted(proxies)[0]
        survivor_nid = sorted(proxies)[1]
        survivor_port = proxies[survivor_nid]["port"]
        victim_handle = ActorHandle(
            proxies[victim_nid]["actor_id"], "HTTPProxy"
        )

        stop = threading.Event()
        survivor_codes = []
        lock = threading.Lock()

        def load():
            url = f"http://127.0.0.1:{survivor_port}/hello"
            while not stop.is_set():
                try:
                    status, _b, _h = _get(url, timeout=10)
                    with lock:
                        survivor_codes.append(status)
                except Exception as e:  # noqa: BLE001
                    with lock:
                        survivor_codes.append(repr(e))

        threads = [threading.Thread(target=load) for _ in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.5)
        victim_pid = ray_tpu.get(victim_handle.pid.remote())
        os.kill(victim_pid, signal.SIGKILL)
        time.sleep(3.0)  # survivor keeps serving through the death
        stop.set()
        for t in threads:
            t.join()
        bad = [c for c in survivor_codes if c != 200]
        assert not bad, f"survivor emitted non-200s during failover: {bad[:5]}"
        assert len(survivor_codes) > 20
        # Routing-table convergence on the survivor (pushed table intact).
        survivor_handle = ActorHandle(
            proxies[survivor_nid]["actor_id"], "HTTPProxy"
        )
        assert ray_tpu.get(survivor_handle.has_route.remote("/hello"))
        # Reconcile loop restores two listening proxies (the restarted one
        # re-binds an ephemeral port and re-registers).
        deadline = time.time() + 60
        while time.time() < deadline:
            ports = {
                nid: p for nid, p in serve.proxy_ports().items()
                if nid != "head" and p
            }
            if len(ports) == 2:
                try:
                    ok = all(_get(f"http://127.0.0.1:{p}/hello", timeout=5)[0] == 200
                             for p in ports.values())
                except OSError:  # registered, not listening yet: the loop is what waits for it
                    ok = False
                if ok:
                    break
            time.sleep(0.5)
        else:
            raise AssertionError(f"proxy fleet never recovered: {ports}")
        serve.shutdown()
    finally:
        cluster.shutdown()


def test_proxy_wire_drain_and_directory():
    """drain_proxy drives the serve_drain/serve_drained wire pair: the
    proxy stops accepting, withdraws from the head's service directory,
    finishes in-flight work, and is removed from the fleet."""
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(head_node_args={"num_cpus": 2})
    try:
        @serve.deployment
        def pong(request):
            return "pong"

        serve.run(pong.bind(), route_prefix="/pong", _blocking_http=False)
        serve.start(proxy_location="EveryNode")
        ports = serve.proxy_ports()
        assert ports

        from ray_tpu._private.worker import global_worker

        directory = global_worker.context.serve_directory()
        assert directory, "bound proxy never announced to the directory"
        assert all("port" in e and "node_id" in e for e in directory)

        controller = serve.api._get_controller()
        nid = sorted(
            nid for nid in serve.proxy_ports() if nid != "head"
        )[0]
        port = serve.proxy_ports()[nid]
        status, _b, _h = _get(f"http://127.0.0.1:{port}/pong")
        assert status == 200
        result = ray_tpu.get(
            controller.drain_proxy.remote(nid, 10.0), timeout=30
        )
        assert result["ok"] is True, result
        # Directory entry withdrawn (serve_proxy_down or worker death).
        deadline = time.time() + 10
        while time.time() < deadline:
            directory = global_worker.context.serve_directory()
            if not any(e.get("port") == port for e in directory):
                break
            time.sleep(0.2)
        else:
            raise AssertionError(f"drained proxy still in directory: {directory}")
        # Fleet registry dropped it.
        proxies = ray_tpu.get(controller.get_proxies.remote())
        assert nid not in proxies
        serve.shutdown()
    finally:
        cluster.shutdown()
