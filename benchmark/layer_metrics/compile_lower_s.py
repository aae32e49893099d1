"""Seconds jax spent lowering jaxprs to MLIR modules in rank 0 over the `fit()`
(`/jax/core/compile/jaxpr_to_mlir_module_duration`; Mosaic kernels are lowered here)."""

from benchmark.harness import bringup

META = {
    "name": "compile.lower_s",
    "unit": "s",
    "better": "lower",
    "source": "program_counter",
    "layer": "compile",
    "moves": "setup_s"
}


def read(run):
    b = bringup.of(run)
    return b.compile.get("lower_s") if b else None
