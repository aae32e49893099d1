"""Device time per step of the operations `step_account.py` finds no owner for: no scope of the program
in their own `op_name`, none in their fusion's instructions, none at the producer of an operand nor at a
user. 0.0 where the trace was read and every operation has an owner. Nothing without a raw trace of a TPU."""

from benchmark.harness import step_account

META = {
    "name": "step.unowned_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "step",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    account = step_account.of(run)
    return account.unowned_ms() if account else None
