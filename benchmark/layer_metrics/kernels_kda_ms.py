"""Device time per step of the channel-wise delta rule's two kernels, `kda_fwd` and `kda_bwd` (`ops/kda.py`), each the median
over the traced steps of its calls' summed time, added up. Nothing where the program has no such kernel."""

from benchmark.harness import program_trace

META = {
    "name": "kernels.kda_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "kernels",
    "moves": "tokens_per_s_per_chip"
}
KERNELS = ('kda_fwd', 'kda_bwd')


def read(run):
    program = program_trace.of(run)
    took = [program.kernel(name) for name in KERNELS] if program else []
    return sum(took) if took and None not in took else None
