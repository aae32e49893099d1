"""Device-busy union inside each execution of the step's program, median of
the traced steps, on rank 0's chip."""

META = {
    "name": "step.device_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "step",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    trace = run["device_trace"]
    return trace.step_device_ms() if trace else None
