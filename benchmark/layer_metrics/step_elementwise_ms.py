"""Device time per step of the class `elementwise` of `step_account.py`: loop and input fusions that
compute, reductions and the elementwise operations outside fusions. The busy union inside the steps at the
median of `step.device_ms`, a nanosecond two classes cover to the first of the account's order, so that the
classes add up to `step.device_ms`. Read off libtpu's `hlo_category` and, for a fusion, the opcodes its `Hlo
Proto` holds. Nothing without a raw trace of a TPU."""

from benchmark.harness import step_account

META = {
    "name": "step.elementwise_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "step",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    account = step_account.of(run)
    return account.classes["elementwise"] if account else None
