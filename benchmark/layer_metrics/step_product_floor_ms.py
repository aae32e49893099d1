"""The least the step's `product` operations (`step.product_ms`) could take: the `flops` XLA counts for
each, a stat of the device plane's event metadata, summed over the steps at the median of `step.device_ms`
and divided by the MXU's peak (`peaks.py`). Flops alone: XLA's `bytes_accessed` counts operands in VMEM as
HBM traffic and is no floor (`step_account.py`). Nothing without a raw trace of a TPU."""

from benchmark.harness import step_account

META = {
    "name": "step.product_floor_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "step",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    account = step_account.of(run)
    return account.product_floor_ms() if account else None
