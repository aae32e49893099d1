"""Device time per step under the scope `shared_expert` of `models/moe.py` (the expert every token
meets beside the routed ones: one SwiGLU outside the sort), forward, recomputation and backward
together, every expert layer of the step: `scope_trace.scope_ms`. Nothing where the program has no
such scope."""

from benchmark.harness import scope_trace

META = {
    "name": "moe.shared_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "expert layer",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return scope_trace.scope_ms(run, ("shared_expert",))
