"""Device time per step under the scope `gdn_conv` of `models/olmo_hybrid.py` (the causal depthwise convolutions of
q, k and v with their SiLU, since PR 54 q and k's L2 norms in the same pass, and the move to heads-first), forward,
recomputation and backward together: `scope_trace.scope_ms`."""

from benchmark.harness import scope_trace

META = {
    "name": "gdn.conv_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "linear attention",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return scope_trace.scope_ms(run, ('gdn_conv',))
