"""Device time per step of the state-space duality scan, whatever implements it: the two Mosaic kernels `ssd_fwd` and
`ssd_bwd` (`ops/ssd.py`), each the median over the traced steps of its calls' summed time, added up; in a program that
runs the scan otherwise, what stands under the scope `ssd_scan` (`scope_trace.scope_ms`: the running sum inside a chunk
with it), so that `kernels.ssd_roofline`'s yardstick outlives the kernels. Nothing where the program has neither."""

from benchmark.harness import program_trace, scope_trace

META = {
    "name": "kernels.ssd_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "kernels",
    "moves": "tokens_per_s_per_chip"
}
KERNELS = ('ssd_fwd', 'ssd_bwd')


def read(run):
    program = program_trace.of(run)
    took = [program.kernel(name) for name in KERNELS] if program else []
    if took and None not in took:
        return sum(took)
    return scope_trace.scope_ms(run, ('ssd_scan',))
