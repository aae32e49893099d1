"""@serve.multiplexed model multiplexing + model-aware routing.

Reference: `python/ray/serve/api.py` @serve.multiplexed,
`serve.get_multiplexed_model_id`, multiplexed-aware router scheduling.
"""

import asyncio

import pytest

import ray_tpu


# ------------------------------------------------------------------ pure async
def test_multiplexed_lru_and_single_flight():
    from ray_tpu.serve.multiplex import multiplexed

    loads = []

    class Host:
        @multiplexed(max_num_models_per_replica=2)
        async def get_model(self, model_id):
            loads.append(model_id)
            await asyncio.sleep(0.01)
            return f"model-{model_id}"

    h = Host()

    async def main():
        # Concurrent same-id requests -> ONE load (single-flight).
        a, b = await asyncio.gather(h.get_model("m1"), h.get_model("m1"))
        assert a == b == "model-m1"
        assert loads == ["m1"]
        await h.get_model("m2")
        # Touch m1 so m2 is the LRU victim when m3 arrives.
        await h.get_model("m1")
        await h.get_model("m3")
        assert loads == ["m1", "m2", "m3"]
        assert set(h.get_model._model_cache.model_ids()) == {"m1", "m3"}
        # m2 was evicted: asking again reloads it.
        await h.get_model("m2")
        assert loads[-1] == "m2"

    asyncio.run(main())


def test_multiplexed_unload_hook_and_errors():
    from ray_tpu.serve.multiplex import multiplexed

    unloaded = []

    class FakeModel:
        def __init__(self, mid):
            self.mid = mid

        def __serve_unload__(self):
            unloaded.append(self.mid)

    class Host:
        @multiplexed(max_num_models_per_replica=1)
        async def get_model(self, model_id):
            if model_id == "bad":
                raise RuntimeError("cannot load")
            return FakeModel(model_id)

    h = Host()

    async def main():
        await h.get_model("a")
        await h.get_model("b")  # evicts a -> __serve_unload__ runs
        assert unloaded == ["a"]
        with pytest.raises(RuntimeError, match="cannot load"):
            await h.get_model("bad")
        # Failed load is not cached; id can be retried.
        with pytest.raises(RuntimeError):
            await h.get_model("bad")

    asyncio.run(main())


def test_multiplexed_requires_async_and_model_id():
    from ray_tpu.serve.multiplex import multiplexed

    with pytest.raises(TypeError, match="async def"):

        @multiplexed
        def sync_loader(self, model_id):
            return None

    with pytest.raises(ValueError):
        multiplexed(max_num_models_per_replica=0)

    class Host:
        @multiplexed
        async def get_model(self, model_id):
            return model_id

    h = Host()

    async def main():
        with pytest.raises(ValueError, match="no model id"):
            await h.get_model()  # no explicit id, no request context

    asyncio.run(main())


# ----------------------------------------------------------------- integration
def test_multiplexed_deployment_handle_and_context(ray_start_regular):
    """Model id flows handle.options -> replica ctxvar -> loader; repeat
    traffic for a model id reuses the cached load (and sticks to the replica
    that holds it)."""
    from ray_tpu import serve

    serve.start(http_options={"location": "NoServer", "port": 0})

    @serve.deployment(max_concurrent_queries=4)
    class Multi:
        def __init__(self):
            self.loads = []

        @serve.multiplexed(max_num_models_per_replica=2)
        async def get_model(self, model_id):
            self.loads.append(model_id)
            return f"weights:{model_id}"

        async def __call__(self, x):
            mid = serve.get_multiplexed_model_id()
            model = await self.get_model()
            return {"model_id": mid, "model": model, "x": x}

        async def load_log(self, _=None):
            return self.loads

    handle = serve.run(Multi.bind(), _blocking_http=False)
    try:
        for i in range(3):
            out = handle.options(multiplexed_model_id="m7").remote(i).result()
            assert out == {"model_id": "m7", "model": "weights:m7", "x": i}
        out2 = handle.options(multiplexed_model_id="m8").remote(99).result()
        assert out2["model"] == "weights:m8"
        loads = handle.load_log.remote().result()
        # 3 requests for m7 -> one load; one for m8.
        assert loads == ["m7", "m8"], loads
    finally:
        serve.shutdown()


def test_multiplexed_over_http_header(ray_start_regular):
    import json
    import urllib.request

    from ray_tpu import serve
    from ray_tpu.serve.multiplex import MODEL_ID_HEADER

    serve.start(http_options={"port": 0})  # a free port: other serve test files hold 8000 on other xdist workers

    @serve.deployment
    class Multi:
        @serve.multiplexed(max_num_models_per_replica=2)
        async def get_model(self, model_id):
            return f"weights:{model_id}"

        async def __call__(self, request):
            model = await self.get_model()
            return {"model": model, "id": serve.get_multiplexed_model_id()}

    serve.run(Multi.bind(), route_prefix="/mm")
    port = serve.http_port()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/mm", data=b"{}", method="POST",
            headers={MODEL_ID_HEADER: "tenant-a"})
        with urllib.request.urlopen(req, timeout=30) as r:
            out = json.loads(r.read())
        assert out == {"model": "weights:tenant-a", "id": "tenant-a"}
    finally:
        serve.shutdown()


def test_multiplexed_streaming_generator(ray_start_regular):
    """Async-generator deployments see the model id too (the pump-task
    context fix): each streamed chunk can consult the request's model."""
    from ray_tpu import serve

    serve.start(http_options={"location": "NoServer", "port": 0})

    @serve.deployment(max_concurrent_queries=2)
    class Streamer:
        @serve.multiplexed(max_num_models_per_replica=2)
        async def get_model(self, model_id):
            return f"w:{model_id}"

        async def __call__(self, n):
            model = await self.get_model()
            for i in range(int(n)):
                yield f"{model}#{i}"

    handle = serve.run(Streamer.bind(), _blocking_http=False)
    try:
        gen = handle.options(
            stream=True, multiplexed_model_id="gmod"
        ).remote(3)
        chunks = list(gen)
        assert chunks == ["w:gmod#0", "w:gmod#1", "w:gmod#2"], chunks
    finally:
        serve.shutdown()


def test_model_affinity_routing(ray_start_regular):
    """With 2 replicas, all traffic for one model id lands on one replica
    (the one that already loaded it)."""
    import os

    from ray_tpu import serve

    serve.start(http_options={"location": "NoServer", "port": 0})

    @serve.deployment(num_replicas=2, max_concurrent_queries=2)
    class Multi:
        @serve.multiplexed(max_num_models_per_replica=2)
        async def get_model(self, model_id):
            return model_id

        async def __call__(self, x):
            await self.get_model()
            return os.getpid()

    handle = serve.run(Multi.bind(), _blocking_http=False)
    try:
        pids = {
            handle.options(multiplexed_model_id="sticky").remote(i).result()
            for i in range(6)
        }
        assert len(pids) == 1, pids
    finally:
        serve.shutdown()


def test_model_affinity_load_escape(ray_start_regular):
    """Affinity routing is load-aware: when the sticky replica is saturated
    (in-flight >= max_concurrent_queries), concurrent traffic for the same
    model escapes to the power-of-two alternative instead of queueing behind
    one replica while the other idles — and the affinity map follows."""
    import os
    import time

    from ray_tpu import serve

    serve.start(http_options={"location": "NoServer", "port": 0})

    @serve.deployment(num_replicas=2, max_concurrent_queries=1)
    class Slow:
        @serve.multiplexed(max_num_models_per_replica=2)
        async def get_model(self, model_id):
            return model_id

        async def __call__(self, x):
            await self.get_model()
            time.sleep(0.3)
            return os.getpid()

    handle = serve.run(Slow.bind(), _blocking_http=False)
    try:
        # Fire a concurrent burst for ONE model id; resolve afterwards. The
        # first call pins the affinity replica; the rest see it saturated
        # and must spread to the second replica.
        resps = [
            handle.options(multiplexed_model_id="hot").remote(i)
            for i in range(6)
        ]
        pids = {r.result() for r in resps}
        assert len(pids) == 2, pids
    finally:
        serve.shutdown()
