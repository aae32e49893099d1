"""Device time per step of the gated delta rule's two kernels, `gdn_fwd` and `gdn_bwd` (`ops/gated_delta_rule.py`), each the
median over the traced steps of its calls' summed time, added up. Nothing where the program has no such kernel."""

from benchmark.harness import program_trace

META = {
    "name": "kernels.gdn_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "kernels",
    "moves": "tokens_per_s_per_chip"
}
KERNELS = ('gdn_fwd', 'gdn_bwd')


def read(run):
    program = program_trace.of(run)
    took = [program.kernel(name) for name in KERNELS] if program else []
    return sum(took) if took and None not in took else None
