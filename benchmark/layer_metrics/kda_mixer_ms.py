"""Device time per step under the scope `kda` of `models/solar_open2.py` (a Kimi Delta Attention mixer whole: its norm, the
four projections, the three convolutions, the two low-rank gates and `beta`, the scan's two kernels, the gated head norm
and `W_o`), forward, recomputation and backward together: `scope_trace.scope_ms`. Nothing where the program has no such scope."""

from benchmark.harness import scope_trace

META = {
    "name": "kda.mixer_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "linear attention",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return scope_trace.scope_ms(run, ('kda',))
