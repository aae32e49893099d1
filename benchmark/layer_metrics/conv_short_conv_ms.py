"""Device time per step under the scope `short_conv` of `models/lfm2.py` (all of the gated short
convolution: the in-projection, `conv_mix` and the out-projection), forward, recomputation and
backward together: `scope_trace.scope_ms`. Nothing where the program has no such scope."""

from benchmark.harness import scope_trace

META = {
    "name": "conv.short_conv_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "short convolution",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return scope_trace.scope_ms(run, ("short_conv",))
