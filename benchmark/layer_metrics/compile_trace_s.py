"""Seconds jax spent tracing jitted functions in rank 0 over the `fit()`
(`/jax/core/compile/jaxpr_trace_duration`, each frame's own seconds: `jax_process.compile_stats`)."""

from benchmark.harness import bringup

META = {
    "name": "compile.trace_s",
    "unit": "s",
    "better": "lower",
    "source": "program_counter",
    "layer": "compile",
    "moves": "setup_s"
}


def read(run):
    b = bringup.of(run)
    return b.compile.get("trace_s") if b else None
