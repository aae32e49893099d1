"""Device time per step under the scope `kda_gates` of `models/solar_open2.py` (the decay's low-rank projection, its softplus
and the move to heads-first, `beta` with its projection of a column a head), forward, recomputation and backward
together: `scope_trace.scope_ms`. Nothing where the program has no such scope."""

from benchmark.harness import scope_trace

META = {
    "name": "kda.gates_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "linear attention",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return scope_trace.scope_ms(run, ('kda_gates',))
