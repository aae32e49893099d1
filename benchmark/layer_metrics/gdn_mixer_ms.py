"""Device time per step under the scope `gdn` of `models/olmo_hybrid.py` (a linear-attention mixer whole: its five
projections, the three convolutions, the gates, the scan's two kernels, the gated norm and `W_o`), forward,
recomputation and backward together: `scope_trace.scope_ms`."""

from benchmark.harness import scope_trace

META = {
    "name": "gdn.mixer_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "linear attention",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return scope_trace.scope_ms(run, ('gdn',))
