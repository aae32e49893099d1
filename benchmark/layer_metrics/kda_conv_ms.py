"""Device time per step under the scope `kda_conv` of `models/solar_open2.py` (`ops/short_conv.py` on q, k and v: the causal
depthwise convolutions with their SiLU, q and k's L2 norms and the move to heads-first; `short_conv_bwd` for the gradient),
forward, recomputation and backward together: `scope_trace.scope_ms`. Nothing where the program has no such scope."""

from benchmark.harness import scope_trace

META = {
    "name": "kda.conv_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "linear attention",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return scope_trace.scope_ms(run, ('kda_conv',))
