"""Device time per step under the scope `mtp` of `models/glm4_moe_lite.py` (the multi-token-prediction
module: its projection of the last layer's activations beside the next token's embedding, its one
expert layer, its norm, the head's product once more and the second cross entropy), forward,
recomputation and backward together: `scope_trace.scope_ms`. Nothing where the program has no such
scope. `mtp` is opened at the top of the loss, where `jax.value_and_grad` wraps the first scope it
meets: the compiled step's `op_name`s carry it as `jvp(mtp)` and `transpose(jvp(mtp))` (as they
carry `jvp(blocks)`, `jvp(head)`), so those are the components looked for beside the bare name."""

from benchmark.harness import scope_trace

META = {
    "name": "mtp.ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "prediction module",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return scope_trace.scope_ms(run, ("mtp", "jvp(mtp)", "transpose(jvp(mtp))"))
