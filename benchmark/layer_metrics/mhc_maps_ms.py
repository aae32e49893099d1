"""Device time per step under `mhc/maps`: the streams' norm, the product with Phi (24 columns over 14,336 values a
token, f32 at full precision) and the two sigmoids, forward, recomputation and backward, every sublayer of the step
(`mhc_mix_ms.under`). Nothing where the program has no such scope."""

from benchmark.layer_metrics import mhc_mix_ms

META = {
    "name": "mhc.maps_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "residual streams",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return mhc_mix_ms.under(run, "mhc", "maps")
