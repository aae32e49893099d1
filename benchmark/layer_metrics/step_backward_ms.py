"""Device time per step of the backward pass (`transpose(jvp(`), recompute excluded: the busy union, inside each run of the
step's program, of the operations whose `op_name` puts them in phase
`backward` (`program_trace.phase`), median of the traced steps on rank 0's
chip. Nothing where the program carries no `jax.named_scope`s."""

from benchmark.harness import program_trace

META = {
    "name": "step.backward_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "step",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    program = program_trace.of(run)
    return program.phase("backward") if program else None
