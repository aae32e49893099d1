"""Device time per step under the scope `ssd_gates` of `models/granite_hybrid.py` (dt's projection and softplus, the log decay dt A and v = dt x, with their gradients; the running sum
inside a chunk is the walk's and stands under `ssd_scan`), forward, recomputation and
backward together: `scope_trace.scope_ms`. Nothing where the program has no such scope."""

from benchmark.harness import scope_trace

META = {
    "name": "ssd.gates_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "linear attention",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return scope_trace.scope_ms(run, ('ssd_gates',))
