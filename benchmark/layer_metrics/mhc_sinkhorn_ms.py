"""Device time per step under `mhc/sinkhorn`: the exponential of the clamped entries and the rounds of row and column
normalisations of a token's n x n matrix, forward, recomputation and backward, every sublayer of the step
(`mhc_mix_ms.under`): latency-bound work on sixteen (rows, positions) planes. Nothing where the program has no such scope."""

from benchmark.layer_metrics import mhc_mix_ms

META = {
    "name": "mhc.sinkhorn_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "residual streams",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return mhc_mix_ms.under(run, "mhc", "sinkhorn")
