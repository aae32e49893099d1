"""Device time per step under the expert layer's scope `experts` (the grouped
matmuls and SwiGLU, whatever computes them), forward, recomputation and
backward together: `scope_trace.scope_ms`."""

from benchmark.harness import scope_trace

META = {
    "name": "moe.experts_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "expert layer",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return scope_trace.scope_ms(run, ('experts',))
