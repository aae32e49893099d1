"""Device time per step under the expert layer's scopes `dispatch` (the sort of the
(token, expert) pairs by expert and the gather of rows into expert order) and
`combine` (the weighting and the sum back per token) together: what the sorted
form costs beside its matmuls. Forward, recomputation and backward alike:
`scope_trace.scope_ms`."""

from benchmark.harness import scope_trace

META = {
    "name": "moe.dispatch_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "expert layer",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return scope_trace.scope_ms(run, ('dispatch', 'combine'))
