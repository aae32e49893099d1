"""How many times jax traced a jitted function in rank 0 over the `fit()`
(`/jax/core/compile/jaxpr_trace_duration` events): a function traced anew at each site shows here."""

from benchmark.harness import bringup

META = {
    "name": "compile.traces",
    "unit": "count",
    "better": "lower",
    "source": "program_counter",
    "layer": "compile",
    "moves": "setup_s"
}


def read(run):
    b = bringup.of(run)
    return b.compile.get("traces") if b else None
