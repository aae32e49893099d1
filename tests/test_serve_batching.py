"""`@serve.batch` dynamic request batching + max_concurrent_queries plumbing.

Reference: `python/ray/serve/batching.py` (@serve.batch),
`max_concurrent_queries` deployment option.
"""

import asyncio

import numpy as np
import pytest

import ray_tpu


# ------------------------------------------------------------------ pure async
def test_batch_coalesces_concurrent_calls():
    from ray_tpu.serve.batching import batch

    class Model:
        def __init__(self):
            self.calls = 0

        @batch(max_batch_size=4, batch_wait_timeout_s=0.2)
        async def predict(self, items):
            self.calls += 1
            return [x * 2 for x in items]

    m = Model()

    async def main():
        return await asyncio.gather(*[m.predict(i) for i in range(8)])

    out = asyncio.run(main())
    assert out == [0, 2, 4, 6, 8, 10, 12, 14]
    # 8 items / max_batch_size 4 -> exactly 2 underlying calls.
    assert m.calls == 2
    assert m.predict._batch_queue.batch_sizes == [4, 4]


def test_batch_flushes_on_timeout():
    from ray_tpu.serve.batching import batch

    class Model:
        @batch(max_batch_size=100, batch_wait_timeout_s=0.05)
        async def predict(self, items):
            return [x + 1 for x in items]

    m = Model()

    async def main():
        loop = asyncio.get_running_loop()
        t0 = loop.time()
        r = await m.predict(41)
        return r, loop.time() - t0

    r, took = asyncio.run(main())
    assert r == 42
    # Flushed by the timeout, not a full batch; don't wait forever.
    assert 0.04 <= took < 1.0, took


def test_batch_error_propagates_to_all_waiters():
    from ray_tpu.serve.batching import batch

    class Model:
        @batch(max_batch_size=4, batch_wait_timeout_s=0.2)
        async def boom(self, items):
            raise RuntimeError("bad batch")

    m = Model()

    async def main():
        return await asyncio.gather(
            *[m.boom(i) for i in range(4)], return_exceptions=True
        )

    out = asyncio.run(main())
    assert len(out) == 4
    assert all(isinstance(e, RuntimeError) and "bad batch" in str(e) for e in out)


def test_batch_wrong_length_return_raises():
    from ray_tpu.serve.batching import batch

    class Model:
        @batch(max_batch_size=4, batch_wait_timeout_s=0.1)
        async def predict(self, items):
            return [1]  # wrong length unless batch was exactly 1... use 2+

    m = Model()

    async def main():
        return await asyncio.gather(
            m.predict(0), m.predict(1), return_exceptions=True
        )

    out = asyncio.run(main())
    assert any(isinstance(e, TypeError) for e in out), out


def test_batch_instances_do_not_share_queues():
    from ray_tpu.serve.batching import batch

    class Model:
        def __init__(self, scale):
            self.scale = scale

        @batch(max_batch_size=2, batch_wait_timeout_s=0.05)
        async def predict(self, items):
            return [x * self.scale for x in items]

    a, b = Model(10), Model(100)

    async def main():
        return await asyncio.gather(a.predict(1), b.predict(1))

    assert asyncio.run(main()) == [10, 100]


def test_batch_requires_async_and_valid_options():
    from ray_tpu.serve.batching import batch

    with pytest.raises(TypeError, match="async def"):

        @batch
        def sync_fn(items):
            return items

    with pytest.raises(ValueError):
        batch(max_batch_size=0)
    with pytest.raises(ValueError):
        batch(batch_wait_timeout_s=-1)


def test_batch_free_function_form():
    from ray_tpu.serve.batching import batch

    seen = []

    @batch(max_batch_size=3, batch_wait_timeout_s=0.1)
    async def double(items):
        seen.append(len(items))
        return [x * 2 for x in items]

    async def main():
        return await asyncio.gather(*[double(i) for i in range(3)])

    assert asyncio.run(main()) == [0, 2, 4]
    assert seen == [3]


def test_batch_queue_rebinds_across_event_loops():
    """asyncio.run twice on the same decorated function must not hang: the
    queue's Event/drainer rebind to the new loop when idle."""
    from ray_tpu.serve.batching import batch

    @batch(max_batch_size=3, batch_wait_timeout_s=0.05)
    async def double(items):
        return [x * 2 for x in items]

    assert asyncio.run(double(1)) == 2
    assert asyncio.run(double(2)) == 4  # second, fresh loop


def test_batch_queue_recovers_from_cancelled_first_loop():
    """Items orphaned by a dead first loop (caller cancelled out of submit)
    must not brick the queue for later loops."""
    from ray_tpu.serve.batching import batch

    @batch(max_batch_size=100, batch_wait_timeout_s=0.3)
    async def echo(items):
        return list(items)

    async def cancelled():
        # Times out long before the flush -> leaves the item queued when the
        # loop dies.
        with pytest.raises(asyncio.TimeoutError):
            await asyncio.wait_for(echo(1), 0.01)

    asyncio.run(cancelled())
    # Fresh loop: the orphaned item is dropped and new calls work.
    assert asyncio.run(echo(42)) == 42


# ----------------------------------------------------------------- integration
def test_serve_batch_over_http(ray_start_regular):
    """Async deployments (and their batch queues) work through the proxy's
    streaming path: concurrent HTTP posts coalesce inside one replica."""
    import concurrent.futures as cf
    import json
    import urllib.request

    from ray_tpu import serve

    serve.start(http_options={"port": 0})

    @serve.deployment(max_concurrent_queries=8)
    class Squarer:
        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.25)
        async def compute(self, xs):
            return [int(x) ** 2 for x in xs]

        async def __call__(self, request):
            return await self.compute(request.json())

    serve.run(Squarer.bind(), route_prefix="/sq", port=0)
    port = serve.http_port()

    def hit(i):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/sq", data=json.dumps(i).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.loads(r.read())

    try:
        with cf.ThreadPoolExecutor(8) as ex:
            out = sorted(ex.map(hit, range(8)))
        assert out == [i * i for i in range(8)], out
    finally:
        serve.shutdown()


def test_sync_deployment_parallel_under_concurrency(ray_start_regular):
    """A blocking sync __call__ with max_concurrent_queries > 1 must run on
    pool threads, NOT serialize on the replica's shared event loop."""
    import concurrent.futures as cf
    import json
    import time as _t
    import urllib.request

    from ray_tpu import serve

    serve.start(http_options={"port": 0})

    @serve.deployment(max_concurrent_queries=4)
    class Slow:
        def __call__(self, request):
            _t.sleep(0.4)
            return "done"

    serve.run(Slow.bind(), route_prefix="/slow", port=0)
    port = serve.http_port()

    def hit(_):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/slow", data=b"{}", method="POST")
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.read().decode()  # string returns are text/plain

    try:
        t0 = _t.monotonic()
        with cf.ThreadPoolExecutor(4) as ex:
            out = list(ex.map(hit, range(4)))
        took = _t.monotonic() - t0
        assert out == ["done"] * 4
        # Serialized would be >= 1.6s; parallel is ~0.4s + overhead.
        assert took < 1.2, took
    finally:
        serve.shutdown()


def test_serve_batch_in_replica(ray_start_regular):
    """One replica with max_concurrent_queries=8: concurrent handle calls
    coalesce into vectorized batches inside the replica."""
    from ray_tpu import serve

    serve.start(http_options={"location": "NoServer", "port": 0})

    @serve.deployment(max_concurrent_queries=8)
    class Doubler:
        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.25)
        async def handle_batch(self, items):
            return [x * 2 for x in items]

        async def __call__(self, x):
            return await self.handle_batch(x)

        async def observed_batches(self, _ignored=None):
            return self.handle_batch._batch_queue.batch_sizes

    handle = serve.run(Doubler.bind(), _blocking_http=False)
    try:
        responses = [handle.remote(i) for i in range(8)]  # all in flight
        out = sorted(r.result() for r in responses)
        assert out == [0, 2, 4, 6, 8, 10, 12, 14]
        sizes = handle.observed_batches.remote().result()
        assert sum(sizes) == 8
        # The whole point: at least one multi-item batch formed.
        assert max(sizes) > 1, sizes
    finally:
        serve.shutdown()
