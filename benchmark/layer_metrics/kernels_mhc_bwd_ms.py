"""Device time per step of the stream mixes' two backward kernels, `mhc_post_bwd` and `mhc_pre_bwd`
(`ops/hyper_connections.py`), each the median over the traced steps of its calls' summed time, added up. The scope's own
time, the forward passes and XLA's share of the backward beside them, is `mhc.mix_ms`. Nothing where the program has no
such kernel."""

from benchmark.harness import program_trace

META = {
    "name": "kernels.mhc_bwd_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "kernels",
    "moves": "tokens_per_s_per_chip"
}
KERNELS = ('mhc_post_bwd', 'mhc_pre_bwd')


def read(run):
    program = program_trace.of(run)
    took = [program.kernel(name) for name in KERNELS] if program else []
    return sum(took) if took and None not in took else None
