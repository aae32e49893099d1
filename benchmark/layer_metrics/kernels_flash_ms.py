"""Summed device time of the Mosaic custom calls (flash forward + fused
backward; the trace cannot name them apart by anything stable) per step."""

META = {
    "name": "kernels.flash_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "kernels",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    trace = run["device_trace"]
    return trace.mosaic_ms() if trace else None
