"""Device time per step of the first pass (embed, blocks, head, loss under `jvp(`): the busy union, inside each run of the
step's program, of the operations whose `op_name` puts them in phase
`forward` (`program_trace.phase`), median of the traced steps on rank 0's
chip. Nothing where the program carries no `jax.named_scope`s."""

from benchmark.harness import program_trace

META = {
    "name": "step.forward_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "step",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    program = program_trace.of(run)
    return program.phase("forward") if program else None
