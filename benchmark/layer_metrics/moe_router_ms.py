"""Device time per step under the expert layer's scope `router` (`models/moe.py`:
the router's matmul, softmax and top-k in float32 and the two auxiliary terms),
forward, recomputation and backward together: `scope_trace.scope_ms`."""

from benchmark.harness import scope_trace

META = {
    "name": "moe.router_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "expert layer",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return scope_trace.scope_ms(run, ('router',))
