"""Device time per step of the Mosaic calls named `ssd_fwd` (`ops/ssd.py`, `pl.pallas_call(name=)`): median over the
traced steps of their summed time, `program_trace.kernel_ms`. Nothing where the program has no kernel of that name."""

from benchmark.harness import program_trace

META = {
    "name": "kernels.ssd_fwd_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "kernels",
    "moves": "tokens_per_s_per_chip"
}
KERNELS = ('ssd_fwd',)


def read(run):
    program = program_trace.of(run)
    took = [program.kernel(name) for name in KERNELS] if program else []
    return sum(took) if took and None not in took else None
