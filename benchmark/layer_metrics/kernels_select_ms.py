"""Device time per step of the Mosaic kernel named `select` (`ops/lightning_indexer.py`, `pl.pallas_call(name=)`): median
over the traced steps, `program_trace.kernel_ms`. Nothing where the program has no kernel of that name."""

from benchmark.harness import program_trace

META = {
    "name": "kernels.select_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "kernels",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    program = program_trace.of(run)
    return program.kernel("select") if program else None
