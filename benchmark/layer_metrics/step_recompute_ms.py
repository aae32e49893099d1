"""Device time per step of the work `jax.checkpoint` runs again in the backward pass
(`rematted_computation`; MFU's 6 N does not count it): the busy union, inside each run of the
step's program, of the operations whose `op_name` puts them in phase
`recompute` (`program_trace.phase`), median of the traced steps on rank 0's
chip. Nothing where the program carries no `jax.named_scope`s."""

from benchmark.harness import program_trace

META = {
    "name": "step.recompute_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "step",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    program = program_trace.of(run)
    return program.phase("recompute") if program else None
