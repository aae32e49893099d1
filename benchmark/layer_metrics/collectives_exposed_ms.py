"""The part of `collectives.total_ms` during which no other operation ran on
that chip."""

META = {
    "name": "collectives.exposed_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "collectives",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    trace = run["device_trace"]
    both = trace.collectives_ms() if trace else None
    return both[1] if both else None
