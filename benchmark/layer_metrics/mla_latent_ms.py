"""Device time per step under the scope `mla_latent` of `models/glm4_moe_lite.py` (all of a latent
attention layer's `qkv_part`: both down-projections, their norms, both up-projections, the rotation
and the broadcast of the shared rotary key), forward, recomputation and backward together, every
attention call of the step: `scope_trace.scope_ms`. Nothing where the program has no such scope."""

from benchmark.harness import scope_trace

META = {
    "name": "mla.latent_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "latent attention",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return scope_trace.scope_ms(run, ("mla_latent",))
