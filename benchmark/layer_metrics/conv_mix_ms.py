"""Device time per step under the scope `conv_mix` of `models/lfm2.py` (what of the short
convolution is no matmul: the gate `B * u`, the three taps, the gate `C * c`), forward,
recomputation and backward together: `scope_trace.scope_ms`."""

from benchmark.harness import scope_trace

META = {
    "name": "conv.mix_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "short convolution",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return scope_trace.scope_ms(run, ("conv_mix",))
