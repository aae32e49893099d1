"""Device time per step of the attention of the full layers of `models/trinity.py` (scope `attention` and the kind's own,
`full`): the two flash kernels under the causal diagonal, forward and backward. Beside `attn.window_ms` it is the two layer
types' unequal cost: one full layer against the window layers of its period. `attn_window_ms.kind_ms`."""

from benchmark.layer_metrics import attn_window_ms

META = {
    "name": "attn.full_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "window attention",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return attn_window_ms.kind_ms(run, attn_window_ms.FULL_KINDS)
