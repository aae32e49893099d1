"""Device time per step of the grouped-matmul kernels of `ops/grouped_matmul.py`:
the Mosaic calls named `gmm_fwd`, `gmm_dlhs` and `gmm_drhs`
(`pl.pallas_call(name=...)`), each the median over the traced steps of its
calls' summed time (`program_trace.kernel_ms`), added up. Nothing where the
program has no such kernel."""

from benchmark.harness import program_trace

META = {
    "name": "kernels.gmm_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "kernels",
    "moves": "tokens_per_s_per_chip"
}
KERNELS = ("gmm_fwd", "gmm_dlhs", "gmm_drhs")


def read(run):
    program = program_trace.of(run)
    took = [program.kernel(name) for name in KERNELS] if program else []
    return sum(took) if took and None not in took else None
