"""Device time per step of the flash kernels: the Mosaic calls whose scope has
a component that starts with `flash_` (`pl.pallas_call(name="flash_fwd" |
"flash_bwd")` in `ops/flash_attention.py`), summed inside each traced step,
median over the steps, so `kernels.flash_fwd_ms + kernels.flash_bwd_ms` is
this. A Mosaic kernel of another name is not counted here: it brings a reader
of its own, and where kernels carry names and none is a flash kernel, or the
run left no raw trace to name them by, nothing is read. Only where no Mosaic
call carries a name at all (a tree before PR 24) does every one count. The
pick is `program_trace.flash_ms`."""

from benchmark.harness import program_trace

META = {
    "name": "kernels.flash_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "kernels",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return program_trace.flash_ms_of(run)
