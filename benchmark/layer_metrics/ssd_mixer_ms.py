"""Device time per step under the scope `ssd` of `models/granite_hybrid.py` (a Mamba-2 mixer whole: its norm, the three column blocks of W_in, the convolution, softplus and decay, the scan's two
kernels, the gate and the norm over all channels, and W_out), forward, recomputation and
backward together: `scope_trace.scope_ms`. Nothing where the program has no such scope."""

from benchmark.harness import scope_trace

META = {
    "name": "ssd.mixer_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "linear attention",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return scope_trace.scope_ms(run, ('ssd',))
