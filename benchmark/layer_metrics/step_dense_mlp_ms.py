"""Device time per step under the scope `dense_mlp` (`models/lfm2.py`, `models/glm4_moe_lite.py`: the leading layers'
dense SwiGLU: three matmuls at `intermediate_size` and the gate between them), forward,
recomputation and backward together: `scope_trace.scope_ms`."""

from benchmark.harness import scope_trace

META = {
    "name": "step.dense_mlp_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "step",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return scope_trace.scope_ms(run, ("dense_mlp",))
