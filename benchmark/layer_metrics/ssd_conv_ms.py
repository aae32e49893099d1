"""Device time per step under the scope `ssd_conv` of `models/granite_hybrid.py` (`ops/short_conv.py` on x, B and C together: the causal depthwise convolution with its bias and SiLU, the
move to heads-first and B's and C's halves put side by side again; `short_conv_bwd` for the gradient), forward, recomputation and
backward together: `scope_trace.scope_ms`. Nothing where the program has no such scope."""

from benchmark.harness import scope_trace

META = {
    "name": "ssd.conv_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "linear attention",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return scope_trace.scope_ms(run, ('ssd_conv',))
