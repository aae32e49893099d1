"""Time per step in which an all-gather, reduce-scatter, all-reduce,
collective-permute or all-to-all was in flight on rank 0's chip."""

META = {
    "name": "collectives.total_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "collectives",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    trace = run["device_trace"]
    both = trace.collectives_ms() if trace else None
    return both[0] if both else None
