"""Device time per step of the Mosaic calls whose scope holds `flash_fwd`
(`pl.pallas_call(name=...)` in `ops/flash_attention.py`), median of the
traced steps. Nothing where the kernels carry no name."""

from benchmark.harness import program_trace

META = {
    "name": "kernels.flash_fwd_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "kernels",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    program = program_trace.of(run)
    return program.kernel(program_trace.FLASH_FWD) if program else None
